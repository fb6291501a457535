"""Jitted Data-Scheduler engine: multi-chain 2-opt as one ``lax.scan``.

Array form of the Sec. VII joint min-max-link-load Hamilton-cycle search
(:func:`repro.core.scheduler.solve_ilp_ls`):

* cycle state is a padded ``[rows, sets, max_n]`` int array where each *row*
  is one (problem, restart-chain) pair — restarts run as parallel chains, and
  :func:`schedule_many` packs a whole batch of sharing problems (pow2-bucketed
  by set count / set size / mesh) into the rows of ONE jitted solve;
* per-pair XY routes come from a dense 0/1 incidence table
  (:func:`_mesh_incidence`, derived from :meth:`MeshNoc.route_table`); a
  cumulative sum of edge-*flip* incidence rows along each cycle turns a
  move's interior link-load delta into two gathers (``flipcum[j] -
  flipcum[i]``) plus four boundary gathers — no scatter, no Python per-edge
  walk;
* each round draws ``moves_per_round`` jax-PRNG proposals per row (uniform
  over the valid ``i < j`` reversal pairs, the degenerate full-cycle reversal
  excluded by rank arithmetic rather than rejection), scores every proposal's
  max-link-load against the current loads (Pallas ``delta_maxload_rows``
  where kernels compile natively, plain ``jnp`` otherwise — see
  :func:`repro.runtime.native_kernels`), applies the best non-worsening move of
  every sharing-set jointly, and exactly re-checks the combined objective —
  falling back to the single globally best move when overlapping routes make
  the combination worse, so the objective is monotone non-increasing like
  the loop reference's sequential best-first rule.

Randomness is batch-independent by construction: every problem's stream is
``fold_in(PRNGKey(Random(seed).getrandbits(32)), crc32(problem))``, so a
problem solved alone (``solve_ilp_ls(backend="scan")``) and the same problem
inside a ``schedule_many`` batch produce bit-identical schedules — which the
mapper's memoized :func:`~repro.core.mapper._sharing_latency` relies on.

Quality contracts (pinned by tests/test_scheduler_engine.py and the
``scheduler_throughput`` benchmark): exact brute-force parity on the small
single-set path, objective <= the loop reference across the Fig. 12 suite,
and per-seed determinism.
"""

from __future__ import annotations

import functools
import random
import zlib
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..core.noc import MeshNoc
from ..core.scheduler import (ScheduleResult, _all_transfers, _finish,
                              _initial_cycles, _solve_exact)
from ..obs import trace
from ..runtime import native_kernels, x64
from .jit_registry import register_jits
from .tuner_train import pow2_bucket

# pad mesh-dependent shapes (node count, link count) to pow2 so every mesh
# with the same padded envelope reuses ONE compiled program — per-mesh
# recompiles, not device compute, dominate a cold campaign's scheduling
# time.  schedule_many(pad_shapes=False) restores the PR 6 exact-shape
# programs (the staged baseline pipeline_throughput measures against);
# results are bit-identical either way (padded links carry zero loads and
# zero deltas through an exact max).
_PAD_SHAPES = True


@functools.lru_cache(maxsize=8)
def _mesh_incidence(noc: MeshNoc, nn_pad: int | None = None,
                    e_pad: int | None = None) -> jax.Array:
    """Dense 0/1 XY-route incidence ``[NN', NN', E']`` int8 for one mesh.

    ``inc[a, b, e] = 1`` iff link ``e`` lies on the XY route ``a -> b`` —
    the gather form of :meth:`MeshNoc.route_table` the jitted 2-opt scores
    deltas against (int8: the largest paper mesh, 16x16, stays at 63 MB).
    ``nn_pad`` / ``e_pad`` zero-pad the node and link axes to a shared
    pow2 envelope (node ids never reach the padded rows; padded links have
    no incidence, so their loads stay exactly zero).  Cached as a
    device-resident ``jax.Array`` so repeat solves on one mesh reuse the
    buffer instead of re-transferring it per dispatch.
    """
    route_pad, _ = noc.route_table()
    nn, e = noc.n_nodes, noc.n_links()
    nn_pad = nn if nn_pad is None else nn_pad
    e_pad = e if e_pad is None else e_pad
    flat = np.zeros((nn * nn, e + 1), dtype=np.int8)
    rows = np.repeat(np.arange(nn * nn), route_pad.shape[2])
    np.add.at(flat, (rows, route_pad.reshape(nn * nn, -1).ravel()), 1)
    inc = np.zeros((nn_pad, nn_pad, e_pad), dtype=np.int8)
    inc[:nn, :nn, :e] = flat[:, :e].reshape(nn, nn, e)
    return jax.device_put(inc)


def _mesh_pads(noc: MeshNoc, pad: bool) -> tuple[int, int]:
    """(node, link) axis sizes for one mesh's jitted state."""
    if not pad:
        return noc.n_nodes, noc.n_links()
    return (pow2_bucket(noc.n_nodes, minimum=4),
            pow2_bucket(noc.n_links(), minimum=8))


# -- the jitted multi-chain search --------------------------------------------


def _randint(key, span, m: int, n_max: int):
    """``jax.random.randint(key, (m,), 0, span)`` under x64, bit for bit.

    x64's ``randint`` draws two uint64 words per value and reduces
    ``(hi * 2**64 + lo) mod span`` in uint64 arithmetic, which the TPU
    emulates at a cost of ~40 s of compile time per scan program.  While
    every span stays below ``2**16`` (set sizes ``n_max <= 362``) the same
    residue is exact in uint32, one 32-bit half-word at a time; larger
    sets take ``jax.random.randint`` itself.
    """
    if n_max * (n_max - 1) // 2 > 1 << 16:
        return jax.random.randint(key, (m,), 0, span)
    s = span.astype(jnp.uint32)
    p32 = (jnp.uint32(1 << 16) % s) ** 2 % s               # 2**32 mod s
    p64 = p32 * p32 % s                                     # 2**64 mod s

    def residue(k):   # one uint64 word mod s
        b = jax.random.bits(k, (m,), jnp.uint64)
        hi, lo = (b >> 32).astype(jnp.uint32), b.astype(jnp.uint32)
        return (hi % s * p32 + lo % s) % s

    k_hi, k_lo = jax.random.split(key)
    return ((residue(k_hi) * p64 + residue(k_lo)) % s).astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("rounds", "n_moves", "use_pallas"),
                   donate_argnums=(0, 3))
def _scan_solve(cycles0, lens, weights, loads0, keys, inc, *,
                rounds: int, n_moves: int, use_pallas: bool):
    """The whole multi-round 2-opt search as one ``lax.scan``.

    ``cycles0 [R, S, N]`` int32 node ids (row = one problem x chain),
    ``lens [R, S]`` true set sizes (0 for padded sets), ``weights [R, S]``
    per-cycle-edge byte weights, ``loads0 [R, E]`` the initial link loads,
    ``keys [R, 2]`` per-row PRNG keys, ``inc [NN, NN, E]`` the mesh's dense
    0/1 route incidence (:func:`_mesh_incidence`).  Every row must have at
    least one eligible (``len >= 4``) set — the host resolves the rest
    without entering the scan.

    Move deltas are scatter-free: reversing ``cyc[i:j+1]`` flips every
    interior edge, and the per-link count of flipping edge ``(a, b)`` is
    ``inc[b, a] - inc[a, b]`` — so one cumulative sum of flip rows along
    each cycle turns a move's interior delta into ``flipcum[j] -
    flipcum[i]`` (two gathers), leaving only the four boundary-edge
    incidence gathers.  Applying is scatter-free too: the best
    non-worsening move per sharing-set is applied jointly (deltas across
    sets add), with an exact re-check of the combined objective — if the
    combination worsens it (overlapping routes), the round falls back to
    the single globally best move, so the objective never increases, the
    same monotonicity the loop reference's sequential best-first rule has.

    ``cycles0`` / ``loads0`` are donated: the caller packs fresh buffers
    per bucket (never the cached ``inc``), so XLA aliases the large padded
    state with the returned ``(cycles, loads)`` instead of allocating a
    second copy.  ``keys`` has no same-shape output to alias and stays
    un-donated.
    """
    R, S, N = cycles0.shape
    E = loads0.shape[1]
    M = n_moves
    ridx = jnp.arange(R)

    def round_body(carry, _):
        cycles, loads, obj, keys = carry
        ks = jax.vmap(lambda k: jax.random.split(k, 3))(keys)
        keys_next, k_si, k_r = ks[:, 0], ks[:, 1], ks[:, 2]
        # -- propose: uniform eligible set, uniform valid (i, j) reversal --
        # width-independent set draw: ONE uniform per proposal, rank-indexed
        # into the eligible sets.  (random.categorical would consume bits
        # shaped [M, S], tying every row's stream to the bucket's padded set
        # axis; this consumes [M] regardless of padding, so the canonical
        # pow4/chunked bucket shapes leave each schedule bit-identical.)
        elig = lens >= 4                                        # [R, S]
        n_elig = jnp.sum(elig, axis=1)                          # [R]
        u = jax.vmap(lambda k: jax.random.uniform(k, (M,)))(k_si)
        idx = jnp.minimum((u * n_elig[:, None]).astype(jnp.int32),
                          n_elig[:, None] - 1)                  # [R, M]
        rank = jnp.cumsum(elig, axis=1) - 1                     # [R, S]
        si = jnp.argmax((rank[:, None, :] == idx[:, :, None])
                        & elig[:, None, :], axis=2)             # [R, M]
        n = jnp.take_along_axis(lens, si, axis=1)               # [R, M]
        # ranks over i<j pairs in (i, j) lexicographic order; the full
        # reversal (0, n-1) has rank n-2 and is skipped by shifting — every
        # draw lands on a real 2-opt move, honoring the move budget
        cnt = n * (n - 1) // 2 - 1
        r = jax.vmap(lambda k, c: _randint(k, c, M, N))(
            k_r, jnp.maximum(cnt, 1))
        r = r + (r >= n - 2)
        t = jnp.minimum(jnp.arange(1, N)[None, None, :], (n - 1)[..., None])
        cum = t * (n[..., None] - 1) - t * (t - 1) // 2
        i = jnp.sum(r[..., None] >= cum, axis=-1)
        j = i + 1 + (r - (i * (n - 1) - i * (i - 1) // 2))
        # -- flip-cumsum per (row, set): interior deltas become gathers ---
        ca, cb = cycles[..., :-1], cycles[..., 1:]              # [R, S, N-1]
        flip = (inc[cb, ca] - inc[ca, cb]).astype(jnp.int16)
        # log-depth associative scan: XLA CPU lowers plain cumsum along a
        # middle axis pathologically (~12x slower here).  int16 halves the
        # memory traffic of the [R, S, N, E] prefix again vs f32 (2.3x on
        # the 960-link 16x16 case) and stays exact: the counts are bounded
        # by the cycle length, far inside the int16 range
        flipcum = jnp.concatenate(
            [jnp.zeros_like(flip[..., :1, :]),
             jax.lax.associative_scan(jnp.add, flip, axis=2)],
            axis=2)                                             # [R, S, N, E]
        fflat = flipcum.reshape(R, S * N, E)

        def fc(pos):   # [R, M] position -> [R, M, E] flipcum row
            return jnp.take_along_axis(fflat, (si * N + pos)[..., None],
                                       axis=1)

        c = jnp.take_along_axis(cycles, si[..., None], axis=1)  # [R, M, N]

        def at(pos):
            return jnp.take_along_axis(c, pos[..., None], axis=2)[..., 0]

        prv = at(jnp.where(i > 0, i - 1, n - 1))
        nxt = at(jnp.where(j + 1 < n, j + 1, 0))
        ci, cj = at(i), at(j)
        bterm = (inc[prv, cj] + inc[ci, nxt]
                 - inc[prv, ci] - inc[cj, nxt]).astype(jnp.int16)
        w = jnp.take_along_axis(weights, si, axis=1)            # [R, M]
        # per-link counts are small exact ints carried in int16; scoring
        # scales them by the set weight in f32 — acceptance is protected
        # by the exact-f64 gate below, never by these scores
        cnt = fc(j) - fc(i) + bterm                             # [R, M, E]
        loads32 = loads.astype(jnp.float32)
        w32 = w.astype(jnp.float32)
        # -- score every proposal against the current loads ---------------
        if use_pallas:
            from ..kernels import dse_eval
            # streamed link tiles + in-kernel count scaling: the f32
            # [R, M, E] delta tensor is never materialized
            objs = dse_eval.delta_maxload_rows(loads32, cnt, w32)
        else:
            delta = cnt.astype(jnp.float32) * w32[..., None]
            objs = jnp.max(loads32[:, None, :] + delta, axis=-1)
        # -- best non-worsening move per set, joint apply with fallback ---
        obj32 = obj.astype(jnp.float32)
        on_set = si[..., None] == jnp.arange(S)[None, None, :]  # [R, M, S]
        objs_s = jnp.where(on_set, objs[..., None], jnp.inf)
        best_m = jnp.argmin(objs_s, axis=1)                     # [R, S]
        valid_s = jnp.min(objs_s, axis=1) <= obj32[:, None]
        m_star = jnp.argmin(objs, axis=1)                       # [R]
        # exact per-set counts of the chosen moves, f64-weighted (the
        # int16 counts convert exactly)
        cnt_s = jnp.take_along_axis(cnt, best_m[..., None],
                                    axis=1).astype(loads.dtype)
        w_s = jnp.where(valid_s, weights, 0.0)                  # [R, S]
        comb = jnp.einsum('rs,rse->re', w_s, cnt_s)             # exact f64
        take_comb = jnp.max(loads + comb, axis=-1) <= obj
        take_single = ~take_comb & (objs[ridx, m_star] <= obj32)
        apply_s = jnp.where(
            take_comb[:, None], valid_s,
            take_single[:, None] & (si[ridx, m_star][:, None]
                                    == jnp.arange(S)[None, :]))
        w_s = jnp.where(apply_s, weights, 0.0)
        cand = loads + jnp.einsum('rs,rse->re', w_s, cnt_s)
        # exact final gate: whatever the scoring precision, a round never
        # leaves the row with a worse objective than it entered with
        new_obj = jnp.max(cand, axis=-1)
        ok = new_obj <= obj
        apply_s = apply_s & ok[:, None]
        loads = jnp.where(ok[:, None], cand, loads)
        obj = jnp.where(ok, new_obj, obj)
        # -- reverse the applied segments in-array ------------------------
        i_s = jnp.take_along_axis(i, best_m, axis=1)            # [R, S]
        j_s = jnp.take_along_axis(j, best_m, axis=1)
        kk = jnp.arange(N)[None, None, :]
        seg = ((kk >= i_s[..., None]) & (kk <= j_s[..., None])
               & apply_s[..., None])
        rev = jnp.where(seg, i_s[..., None] + j_s[..., None] - kk, kk)
        cycles = jnp.take_along_axis(cycles, rev, axis=2)
        return (cycles, loads, obj, keys_next), None

    obj0 = jnp.max(loads0, axis=-1)
    (cycles, loads, obj, _), _ = jax.lax.scan(
        round_body, (cycles0, loads0, obj0, keys), None, length=rounds)
    return cycles, loads, obj


# -- host-side problem packing ------------------------------------------------


@dataclass
class _Setup:
    """One problem either pre-resolved or packed for the jitted search."""

    noc: MeshNoc
    sets: tuple[tuple[int, ...], ...]
    chunks: tuple[float, ...]
    resolve: str | None = None             # "exact" | "inits" | None (scan)
    inits: list[list[list[int]]] | None = None   # [chain][set] node order
    seed_eff: int = 0                      # Random(seed).getrandbits(32)
    digest: int = 0                        # crc32 problem stream id


def _problem_digest(noc: MeshNoc, sets, chunks, restarts: int, iters: int,
                    moves_per_round: int) -> int:
    """Stable per-problem stream id — batch composition must not matter."""
    return zlib.crc32(repr((noc.rows, noc.cols, sets, chunks, restarts,
                            iters, moves_per_round)).encode())


def _best_of(noc: MeshNoc, candidates, chunks) -> ScheduleResult | None:
    """First-strict-best candidate cycles by exact recomputed objective."""
    best, best_obj = None, np.inf
    for cycles in candidates:
        obj = noc.max_link_load(_all_transfers(cycles, list(chunks)))
        if obj < best_obj:
            best, best_obj = cycles, obj
    return best


def _setup_problem(noc: MeshNoc, sets, chunks, *, rng: random.Random,
                   restarts: int, iters: int,
                   moves_per_round: int) -> _Setup:
    """Normalize one problem; resolve it host-side when the scan can't help.

    Mirrors ``solve_ilp_ls``'s structure: the small single-set path is
    exhaustive, and a problem with no 2-opt-eligible set (every cycle
    shorter than 4 nodes) reduces to picking the best restart
    initialization — exactly what the loop reference does when
    ``_propose_moves`` comes back empty.
    """
    sets = tuple(tuple(s) for s in sets)
    chunks = tuple(float(c) for c in chunks)
    setup = _Setup(noc=noc, sets=sets, chunks=chunks)
    seed_eff = rng.getrandbits(32)
    if len(sets) == 1 and len(sets[0]) <= 7:
        setup.resolve = "exact"   # sentinel: caller runs _solve_exact
        return setup
    chains = max(3, restarts)
    inits = [_initial_cycles(noc, [list(s) for s in sets], r, rng)
             for r in range(chains)]
    if not any(len(s) >= 4 for s in sets):
        setup.resolve = "inits"   # sentinel: caller picks the best init
        setup.inits = inits
        return setup
    setup.inits = inits
    setup.seed_eff = seed_eff
    setup.digest = _problem_digest(noc, sets, chunks, restarts, iters,
                                   moves_per_round)
    return setup


def _rounds(iters: int, moves_per_round: int) -> int:
    return max(1, -(-iters // moves_per_round))


# fixed row-axis size for canonical (pad_shapes) buckets: bigger buckets run
# as several 32-row dispatches of ONE program, smaller ones pad up to it
_R_CHUNK = 32
_SOLO_EXACT_LINKS = 512   # solo solves on meshes at least this wide get
                          # exact (pow2) rows instead of the canonical chunk


def _pow4_bucket(n: int, minimum: int) -> int:
    """Next power of FOUR >= max(n, minimum) — the coarse program-key class.

    Under ``pad_shapes`` the set-count and set-size axes quantize to pow4
    instead of pow2: both axes are fully masked (padded sets carry zero
    weight and zero length, padded tail slots sit past every row's true
    length), so coarser padding is bit-safe and halves the number of
    distinct compiled programs per mesh envelope — at most 4x padded work
    on one axis, against a ~1.4 s XLA compile saved per collapsed shape.
    """
    p = pow2_bucket(n, minimum=minimum)
    return p * 2 if (p.bit_length() - 1) % 2 else p


def _bucket_key(st: _Setup, pad_shapes: bool) -> tuple:
    """(mesh, padded set count, padded max set size) — one jit program each.

    With ``pad_shapes`` the class bounds are pow4 (see :func:`_pow4_bucket`)
    so problems with nearby shapes share a bucket AND a compiled program;
    without it they are exact pow2, the PR 6 per-shape behavior.
    """
    pad = _pow4_bucket if pad_shapes else pow2_bucket
    return (st.noc, pad(len(st.sets), minimum=1),
            pad(max(len(s) for s in st.sets), minimum=4))


def _resolve_host(st: _Setup, link_bw: float, freq: float,
                  pj_per_bit_hop: float) -> ScheduleResult | None:
    """Finish a pre-resolved (small/no-eligible-move) setup; None if it
    needs the jitted search."""
    if st.resolve == "exact":
        return _solve_exact(st.noc, [list(s) for s in st.sets],
                            list(st.chunks), link_bw, freq, pj_per_bit_hop)
    if st.resolve == "inits":
        best = _best_of(st.noc, st.inits, st.chunks)
        return _finish(st.noc, best, list(st.chunks), link_bw, freq,
                       pj_per_bit_hop)
    return None


def _finish_chains(st: _Setup, per_chain, link_bw: float, freq: float,
                   pj_per_bit_hop: float) -> ScheduleResult:
    """Pick a setup's best chain by exact recompute and build the result.

    Re-deriving every chain's objective from the transfers themselves (the
    loop reference's restart comparison) keeps the winner free of any
    accumulated in-array delta round-off.
    """
    best = _best_of(st.noc, per_chain, st.chunks)
    return _finish(st.noc, best, list(st.chunks), link_bw, freq,
                   pj_per_bit_hop)


@jax.jit
def _fold_keys(seeds, digests, chains):
    """Per-row PRNG keys ``fold_in(fold_in(PRNGKey(seed), digest), chain)``.

    One vmapped dispatch per bucket instead of two ``fold_in`` round-trips
    per (problem, chain) row — the derivation itself (and therefore every
    schedule) is unchanged.
    """
    def one(se, dg, c):
        return jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(se), dg), c)
    return jax.vmap(one)(seeds, digests, chains)


#: module-level jit objects, keyed for ``compiled_program_count``-style
#: introspection (see :func:`repro.engine.engine_program_counts`),
#: registered at creation time
_JITTED = register_jits(
    scan_solve=_scan_solve,
    fold_keys=_fold_keys,
)


def _run_bucket(setups: list[_Setup], *, rounds: int, moves_per_round: int,
                s_pad: int, n_pad: int, use_pallas: bool,
                pad_shapes: bool = True) -> list[list]:
    """Solve one bucket's problems in lockstep; returns per-problem chains
    (each a ``[chain][set] -> node order`` nested list).

    Every problem in a bucket shares the mesh and the padded (sets, set
    size) envelope; rows of the jitted state are (problem x chain) pairs.
    With ``pad_shapes`` the row axis is CANONICAL: buckets run as chunks of
    exactly ``_R_CHUNK`` rows (larger buckets become several dispatches of
    one program, smaller ones pad up), and the mesh axes are pow2-padded,
    so different meshes with the same padded envelope share ONE compiled
    program (the incidence table is a runtime argument — only shapes key
    the jit cache).  Without it the row axis is the exact pow2 bucket of
    the batch, the PR 6 per-shape behavior.  Rows are independent (one
    PRNG stream each; padded rows burn copies of row 0), so chunking and
    padding leave every problem's schedule bit-identical.
    """
    chains = len(setups[0].inits)
    solo_exact = (len(setups) == 1
                  and setups[0].noc.n_links() >= _SOLO_EXACT_LINKS)
    if not pad_shapes or chains > _R_CHUNK or solo_exact:
        # exact rows when canonicalization is off, when one problem's
        # chains overflow a chunk, or for a SOLO solve on a big mesh: a
        # single 6-chain Fig. 12 16x16 solve (960 links) is memory-bound
        # in its dense link state and must not burn 26 padded rows.  On
        # small meshes burner rows are nearly free, so solos keep the
        # canonical chunk width and share the batched bucket's program.
        # (Row count never shifts a chain's PRNG stream — each row folds
        # its own key — so this only changes cost, never results.)
        r_pad = pow2_bucket(len(setups) * chains, minimum=4)
        per = len(setups)
    else:
        r_pad = _R_CHUNK
        per = max(1, _R_CHUNK // chains)
    results: list[list] = []
    for lo in range(0, len(setups), per):
        results.extend(_pack_solve(
            setups[lo:lo + per], rounds=rounds,
            moves_per_round=moves_per_round, s_pad=s_pad, n_pad=n_pad,
            r_pad=r_pad, use_pallas=use_pallas, pad_shapes=pad_shapes))
    return results


def _pack_solve(setups: list[_Setup], *, rounds: int, moves_per_round: int,
                s_pad: int, n_pad: int, r_pad: int, use_pallas: bool,
                pad_shapes: bool) -> list[list]:
    """Pack one row-chunk of setups and run the jitted search at ``r_pad``.

    All inputs go through explicit ``jax.device_put``: the engine performs
    no implicit host->device transfers (``tests/test_pipeline.py`` runs
    this under ``jax.transfer_guard("disallow")``).
    """
    noc = setups[0].noc
    chains = len(setups[0].inits)
    _, e_pad = _mesh_pads(noc, pad_shapes)
    rows = len(setups) * chains
    cycles0 = np.zeros((r_pad, s_pad, n_pad), dtype=np.int32)
    lens = np.zeros((r_pad, s_pad), dtype=np.int32)
    weights = np.zeros((r_pad, s_pad))
    loads0 = np.zeros((r_pad, e_pad))
    keys = np.zeros((r_pad, 2), dtype=np.uint32)
    e = noc.n_links()
    for p, st in enumerate(setups):
        for c, init in enumerate(st.inits):
            row = p * chains + c
            for si, cyc in enumerate(init):
                cycles0[row, si, :len(cyc)] = cyc
                lens[row, si] = len(cyc)
                weights[row, si] = (len(cyc) - 1) * st.chunks[si]
            loads0[row, :e] = noc.link_loads_np(
                _all_transfers(init, list(st.chunks)))
    folded = _fold_keys(
        jax.device_put(np.array(
            [st.seed_eff for st in setups for _ in range(chains)],
            dtype=np.uint32)),
        jax.device_put(np.array(
            [st.digest for st in setups for _ in range(chains)],
            dtype=np.uint32)),
        jax.device_put(np.arange(rows, dtype=np.uint32) % chains))
    # keys feed the host-side packed arrays: one pull per bucket, before
    # the scan dispatch
    with trace.span("device_wait", cat="engine", what="fold_keys"):
        # pimlint: disable-next-line=host-sync -- sanctioned per-bucket key pull
        keys[:rows] = np.asarray(folded, dtype=np.uint32)
    for row in range(rows, r_pad):   # padded rows: burn a copy of row 0
        cycles0[row], lens[row] = cycles0[0], lens[0]
        weights[row], loads0[row], keys[row] = (weights[0], loads0[0],
                                                keys[0])
    with x64():
        inc = (_mesh_incidence(noc, *_mesh_pads(noc, True)) if pad_shapes
               else _mesh_incidence(noc))
        # cycles0/loads0 are donated by _scan_solve — freshly packed per
        # bucket, so handing the buffers over is safe
        out_cycles, _, _ = _scan_solve(
            jax.device_put(cycles0), jax.device_put(lens),
            jax.device_put(weights), jax.device_put(loads0),
            jax.device_put(keys), inc,
            rounds=rounds, n_moves=moves_per_round, use_pallas=use_pallas)
    with trace.span("device_wait", cat="engine", what="scan_solve"):
        # pimlint: disable-next-line=host-sync -- the one result pull per bucket
        out_cycles = np.asarray(out_cycles)
    results = []
    for p, st in enumerate(setups):
        per_chain = []
        for c in range(chains):
            row = p * chains + c
            per_chain.append([
                [int(v) for v in out_cycles[row, si, :len(s)]]
                for si, s in enumerate(st.sets)])
        results.append(per_chain)
    return results


def schedule_many(problems, link_bw: float, freq: float,
                  pj_per_bit_hop: float, *, seed: int = 0,
                  restarts: int = 4, iters: int = 400,
                  moves_per_round: int = 32,
                  use_pallas: bool | None = None,
                  pad_shapes: bool | None = None) -> list[ScheduleResult]:
    """Solve a batch of ``(noc, sharing_sets, chunk_bytes)`` problems.

    Problems are pow2-bucketed by (mesh, set count, max set size) and each
    bucket runs through ONE jitted multi-chain search; small or
    no-eligible-move problems resolve host-side exactly like
    ``solve_ilp_ls``.  Each element equals the single-problem
    ``solve_ilp_ls(..., backend="scan", seed=seed)`` result bit-for-bit —
    per-problem PRNG streams make results independent of batch composition,
    so the mapper's schedule memo can be prefilled batch-wise.

    ``pad_shapes`` (default: the module's ``_PAD_SHAPES``, True) pow2-pads
    the mesh axes AND canonicalizes the bucket shape — pow4 set-count/
    set-size classes, fixed ``_R_CHUNK``-row dispatches — so distinct
    meshes and nearby problem shapes share compiled programs; results are
    bit-identical with or without padding — only compile count changes.
    """
    use_pallas = native_kernels() if use_pallas is None else use_pallas
    pad_shapes = _PAD_SHAPES if pad_shapes is None else pad_shapes
    rounds = _rounds(iters, moves_per_round)
    with trace.span("schedule_many", cat="engine",
                    problems=len(problems)) as sp:
        results: list[ScheduleResult | None] = [None] * len(problems)
        buckets: dict[tuple, list[tuple[int, _Setup]]] = {}
        for pi, (noc, sets, chunks) in enumerate(problems):
            st = _setup_problem(noc, sets, chunks, rng=random.Random(seed),
                                restarts=restarts, iters=iters,
                                moves_per_round=moves_per_round)
            results[pi] = _resolve_host(st, link_bw, freq, pj_per_bit_hop)
            if results[pi] is None:
                buckets.setdefault(_bucket_key(st, pad_shapes),
                                   []).append((pi, st))
        for (mesh, s_pad, n_pad), entries in buckets.items():
            nn_pad, e_pad = _mesh_pads(mesh, pad_shapes)
            with trace.span("schedule", cat="engine",
                            bucket=f"{mesh}:{s_pad}x{n_pad}",
                            envelope=f"{nn_pad}n{e_pad}e",
                            problems=len(entries)):
                chains = _run_bucket([st for _, st in entries],
                                     rounds=rounds,
                                     moves_per_round=moves_per_round,
                                     s_pad=s_pad, n_pad=n_pad,
                                     use_pallas=use_pallas,
                                     pad_shapes=pad_shapes)
            for (pi, st), per_chain in zip(entries, chains):
                results[pi] = _finish_chains(st, per_chain, link_bw, freq,
                                             pj_per_bit_hop)
        sp["buckets"] = len(buckets)
        sp["host_resolved"] = len(problems) - sum(
            len(v) for v in buckets.values())
    return results


def _solve_one_scan(noc: MeshNoc, sharing_sets, chunk_bytes, link_bw: float,
                    freq: float, pj_per_bit_hop: float, *,
                    rng: random.Random, restarts: int, iters: int,
                    moves_per_round: int) -> ScheduleResult:
    """``solve_ilp_ls``'s scan backend: one problem through the engine.

    Identical resolution sequence to :func:`schedule_many` (shared helpers)
    — only the RNG comes from the caller, so an explicit ``rng`` keeps
    working like the loop backend's contract.
    """
    st = _setup_problem(noc, sharing_sets, chunk_bytes, rng=rng,
                        restarts=restarts, iters=iters,
                        moves_per_round=moves_per_round)
    got = _resolve_host(st, link_bw, freq, pj_per_bit_hop)
    if got is not None:
        return got
    _, s_pad, n_pad = _bucket_key(st, _PAD_SHAPES)
    per_chain = _run_bucket([st], rounds=_rounds(iters, moves_per_round),
                            moves_per_round=moves_per_round, s_pad=s_pad,
                            n_pad=n_pad, use_pallas=native_kernels(),
                            pad_shapes=_PAD_SHAPES)[0]
    return _finish_chains(st, per_chain, link_bw, freq, pj_per_bit_hop)
