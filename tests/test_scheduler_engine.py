"""Engine Data-Scheduler: jitted multi-chain 2-opt + batched scheduling.

Pins the PR's quality contracts: exact brute-force parity on small sets,
scan <= loop across the Fig. 12 arrays, per-backend seed determinism,
batch-independence of ``schedule_many``, the vectorized NoC load model, the
``_two_opt_distance`` delta rewrite, the ``_propose_moves`` budget fix, and
numpy parity of the Pallas ``delta_maxload_rows`` kernel.
"""

import itertools
import random

import numpy as np
import pytest

from repro.core.noc import MeshNoc
from repro.core.scheduler import (SOLVERS, _all_transfers, _apply_2opt,
                                  _initial_cycles, _propose_moves,
                                  _two_opt_distance, solve_ilp_ls, solve_shp,
                                  solve_tsp)
from repro.engine.scheduler_opt import schedule_many

BW, FREQ, EPJ = 3.2e9, 400e6, 1.1
SOLVE_KW = dict(seed=0, restarts=4, iters=200, moves_per_round=16)


def fig12_sets(dim: int, stride: int):
    noc = MeshNoc(dim, dim)
    sets = [[noc.node(r * stride + oy, c * stride + ox)
             for r in range(4) for c in range(4)]
            for oy in range(stride) for ox in range(stride)]
    return noc, sets


# ---------------------------------------------------------------------------
# vectorized NoC load model
# ---------------------------------------------------------------------------


def _ref_link_loads(noc, transfers):
    loads = [0.0] * noc.n_links()
    for src, dst, nbytes in transfers:
        if src == dst or nbytes <= 0:
            continue
        for l in noc.route(src, dst):
            loads[l] += nbytes
    return loads


def test_link_loads_vectorized_parity():
    rng = random.Random(0)
    for rows, cols in ((1, 4), (3, 3), (4, 4), (8, 8)):
        noc = MeshNoc(rows, cols)
        nn = noc.n_nodes
        for _ in range(10):
            tr = [(rng.randrange(nn), rng.randrange(nn),
                   rng.choice([0.0, -5.0, rng.uniform(1, 1e6)]))
                  for _ in range(rng.randrange(0, 10))]
            ref = _ref_link_loads(noc, tr)
            np.testing.assert_allclose(noc.link_loads_np(tr), ref)
            assert noc.link_loads(tr) == ref  # list API preserved
            ref_e = sum(b * 8 * noc.hops(s, d) * EPJ for s, d, b in tr)
            assert noc.transfer_energy_pj(tr, EPJ) == pytest.approx(ref_e)


def test_route_table_matches_routes():
    noc = MeshNoc(3, 4)
    pad, hops = noc.route_table()
    for a in range(noc.n_nodes):
        for b in range(noc.n_nodes):
            r = noc.route(a, b)
            assert hops[a, b] == len(r) == noc.hops(a, b)
            assert tuple(pad[a, b, :len(r)]) == r
            assert (pad[a, b, len(r):] == noc.n_links()).all()


# ---------------------------------------------------------------------------
# TSP baseline: O(1) delta scoring must keep the full-recompute result
# ---------------------------------------------------------------------------


def _two_opt_distance_ref(noc, cyc):
    def total(c):
        return sum(noc.hops(c[i], c[(i + 1) % len(c)]) for i in range(len(c)))
    best = list(cyc)
    best_d = total(best)
    improved = True
    while improved:
        improved = False
        for i in range(1, len(best) - 1):
            for j in range(i + 1, len(best)):
                cand = _apply_2opt(best, i, j)
                d = total(cand)
                if d < best_d:
                    best, best_d = cand, d
                    improved = True
    return best


def test_two_opt_distance_delta_matches_full_recompute():
    rng = random.Random(1)
    noc = MeshNoc(5, 5)
    for _ in range(25):
        n = rng.randint(4, 10)
        cyc = rng.sample(range(noc.n_nodes), n)
        assert _two_opt_distance(noc, cyc) == _two_opt_distance_ref(noc, cyc)


# ---------------------------------------------------------------------------
# _propose_moves: full budget, no degenerate full reversals
# ---------------------------------------------------------------------------


def test_propose_moves_honors_budget():
    rng = random.Random(2)
    # size-4 cycles draw the excluded (0, n-1) pair with probability 1/5
    # per move — the old skip-not-redraw under-filled these heavily
    cycles = [[0, 1, 2, 3], [4, 5, 6, 7]]
    for _ in range(50):
        moves = _propose_moves(cycles, rng, 16)
        assert len(moves) == 16
        for si, i, j in moves:
            assert 0 <= i < j <= 3
            assert (i, j) != (0, 3)
    assert _propose_moves([[0, 1, 2]], rng, 8) == []  # nothing eligible


# ---------------------------------------------------------------------------
# property: reported objective == recompute, across every solver/backend
# ---------------------------------------------------------------------------


def _solver_calls():
    for name in SOLVERS:
        if name == "ilp":
            for backend in ("scan", "loop"):
                yield f"ilp/{backend}", dict(backend=backend)
        else:
            yield name, {}


@pytest.mark.parametrize("seed", [0, 3])
def test_reported_max_link_bytes_is_exact(seed):
    noc, sets = fig12_sets(4, 1)
    sets = [sets[0][:8], [n + 8 for n in sets[0][:8]]]
    chunks = [1000.0, 2500.0]
    for label, extra in _solver_calls():
        solver = SOLVERS[label.split("/")[0]]
        res = solver(noc, sets, chunks, BW, FREQ, EPJ, seed=seed,
                     **({"restarts": 3, "iters": 100} if "ilp" in label
                        else {}), **extra)
        assert res.max_link_bytes == pytest.approx(
            noc.max_link_load(res.transfers)), label
        if res.cycles:  # cycle solvers: transfers must derive from cycles
            rebuilt = _all_transfers(res.cycles, chunks)
            assert sorted(rebuilt) == sorted(res.transfers), label


@pytest.mark.parametrize("label_extra", list(_solver_calls()))
def test_seed_determinism_every_solver(label_extra):
    label, extra = label_extra
    noc, sets = fig12_sets(4, 1)
    sets = [sets[0][:8], [n + 8 for n in sets[0][:8]]]
    chunks = [4096.0, 4096.0]
    solver = SOLVERS[label.split("/")[0]]
    kw = dict(seed=7, **({"restarts": 3, "iters": 100}
                         if "ilp" in label else {}), **extra)
    a = solver(noc, sets, chunks, BW, FREQ, EPJ, **kw)
    b = solver(noc, sets, chunks, BW, FREQ, EPJ, **kw)
    assert a.cycles == b.cycles
    assert a.transfers == b.transfers
    assert a.max_link_bytes == b.max_link_bytes


def test_scan_rng_equals_seed():
    noc, sets = fig12_sets(4, 1)
    sets = [sets[0][:8], [n + 8 for n in sets[0][:8]]]
    chunks = [1024.0, 2048.0]
    a = solve_ilp_ls(noc, sets, chunks, BW, FREQ, EPJ, **SOLVE_KW)
    c = solve_ilp_ls(noc, sets, chunks, BW, FREQ, EPJ,
                     rng=random.Random(SOLVE_KW["seed"]),
                     **{k: v for k, v in SOLVE_KW.items() if k != "seed"})
    assert a.cycles == c.cycles


def test_unknown_backend_raises():
    noc = MeshNoc(2, 2)
    with pytest.raises(ValueError, match="backend"):
        solve_ilp_ls(noc, [[0, 1, 2, 3]], [1.0], BW, FREQ, EPJ,
                     backend="vector")


# ---------------------------------------------------------------------------
# quality: brute force on small sets, scan <= loop on the Fig. 12 arrays
# ---------------------------------------------------------------------------


def test_scan_small_single_set_is_exact():
    """The small path brute-forces — identical through either backend."""
    noc = MeshNoc(3, 3)
    nodes = [0, 1, 3, 4, 8]
    chunk = 1000.0
    best = min(noc.max_link_load(_all_transfers([[nodes[0]] + list(p)],
                                                [chunk]))
               for p in itertools.permutations(nodes[1:]))
    for backend in ("scan", "loop"):
        res = solve_ilp_ls(noc, [nodes], [chunk], BW, FREQ, EPJ,
                           backend=backend)
        assert res.max_link_bytes == pytest.approx(best)


def test_scan_two_small_sets_match_joint_bruteforce():
    """The jitted search itself (not the exact path) finds the optimum."""
    noc = MeshNoc(2, 4)
    sets = [[0, 1, 4, 5], [2, 3, 6, 7]]
    chunks = [1000.0, 1500.0]
    best = min(
        noc.max_link_load(_all_transfers(
            [[sets[0][0]] + list(p), [sets[1][0]] + list(q)], chunks))
        for p in itertools.permutations(sets[0][1:])
        for q in itertools.permutations(sets[1][1:]))
    res = solve_ilp_ls(noc, sets, chunks, BW, FREQ, EPJ, seed=0,
                       restarts=4, iters=400, backend="scan")
    assert res.max_link_bytes == pytest.approx(best)


@pytest.mark.parametrize("dim,stride", [(4, 1), (8, 2)])
def test_scan_not_worse_than_loop_fig12(dim, stride):
    noc, sets = fig12_sets(dim, stride)
    chunks = [8192.0] * len(sets)
    kw = dict(seed=0, restarts=4, iters=400)
    scan = solve_ilp_ls(noc, sets, chunks, BW, FREQ, EPJ, backend="scan",
                        **kw)
    loop = solve_ilp_ls(noc, sets, chunks, BW, FREQ, EPJ, backend="loop",
                        **kw)
    assert scan.max_link_bytes <= loop.max_link_bytes + 1e-9
    # both monotone searches start from the TSP seed: never worse than it
    tsp = solve_tsp(noc, sets, chunks, BW, FREQ, EPJ)
    assert scan.max_link_bytes <= tsp.max_link_bytes + 1e-9
    assert loop.max_link_bytes <= tsp.max_link_bytes + 1e-9


def test_scan_loads_match_cycles_exactly():
    """The scan's in-array delta accumulation must not drift from the
    objective recomputed from its returned cycles."""
    noc, sets = fig12_sets(4, 1)
    res = solve_ilp_ls(noc, sets, [8192.0], BW, FREQ, EPJ, **SOLVE_KW)
    assert sorted(res.cycles[0]) == sorted(sets[0])   # still a permutation
    assert res.max_link_bytes == pytest.approx(
        noc.max_link_load(_all_transfers(res.cycles, [8192.0])))


# ---------------------------------------------------------------------------
# schedule_many: lockstep multi-problem solving, batch independence
# ---------------------------------------------------------------------------


def test_schedule_many_matches_single_solves():
    noc4 = MeshNoc(4, 4)
    noc24 = MeshNoc(2, 4)
    problems = [
        # small single set: exact path
        (noc24, [[0, 1, 5]], [512.0]),
        # no 2-opt-eligible set: best-init path
        (noc4, [[0, 1, 2], [4, 5, 6]], [256.0, 256.0]),
        # scan problems, two different meshes and set counts
        (noc4, [[0, 1, 2, 3, 4, 5, 6, 7]], [1024.0]),
        (noc4, [[0, 1, 2, 3, 4, 5, 6, 7],
                [8, 9, 10, 11, 12, 13, 14, 15]], [1024.0, 2048.0]),
        (noc24, [[0, 1, 2, 3, 4, 5, 6, 7]], [4096.0]),
        # duplicate of an earlier problem: must resolve identically
        (noc4, [[0, 1, 2, 3, 4, 5, 6, 7]], [1024.0]),
    ]
    kw = dict(seed=3, restarts=4, iters=200, moves_per_round=16)
    batched = schedule_many(problems, BW, FREQ, EPJ, **kw)
    for k, (noc, sets, chunks) in enumerate(problems):
        single = solve_ilp_ls(noc, sets, chunks, BW, FREQ, EPJ,
                              backend="scan", **kw)
        assert single.cycles == batched[k].cycles, k
        assert single.max_link_bytes == batched[k].max_link_bytes, k
        assert single.latency_s == batched[k].latency_s, k
    assert batched[2].cycles == batched[5].cycles  # duplicates agree


def test_schedule_many_independent_of_batch_composition():
    noc = MeshNoc(4, 4)
    prob = (noc, [[0, 1, 2, 3, 4, 5, 6, 7]], [4096.0])
    other = (noc, [[8, 9, 10, 11, 12, 13, 14, 15]], [512.0])
    kw = dict(seed=1, restarts=4, iters=200, moves_per_round=16)
    alone = schedule_many([prob], BW, FREQ, EPJ, **kw)[0]
    together = schedule_many([other, prob, other], BW, FREQ, EPJ, **kw)[1]
    assert alone.cycles == together.cycles
    assert alone.max_link_bytes == together.max_link_bytes


def test_no_eligible_sets_matches_loop():
    """With no 2-opt-eligible cycle both backends reduce to best-init."""
    noc = MeshNoc(4, 4)
    sets = [[0, 1, 5], [2, 3, 7]]
    chunks = [4096.0, 4096.0]
    scan = solve_ilp_ls(noc, sets, chunks, BW, FREQ, EPJ, backend="scan")
    loop = solve_ilp_ls(noc, sets, chunks, BW, FREQ, EPJ, backend="loop")
    assert scan.max_link_bytes == loop.max_link_bytes
    assert scan.cycles == loop.cycles


@pytest.mark.parametrize("n_max", [256, 400], ids=["uint32", "fallback"])
def test_scan_randint_matches_jax_randint(n_max):
    """The scan's move draws equal x64 ``jax.random.randint`` bit for bit,
    over every span a set of ``n_max`` nodes can give."""
    import jax
    import jax.numpy as jnp
    from repro.engine.scheduler_opt import _randint
    from repro.runtime import x64
    rng = np.random.default_rng(3)
    top = n_max * (n_max - 1) // 2 - 1
    spans = np.concatenate([np.arange(1, 600), [top - 1, top],
                            rng.integers(1, top + 1, 4000)]).astype(np.int32)
    with x64():
        keys = jax.vmap(jax.random.PRNGKey)(
            jnp.asarray(rng.integers(0, 2**31, spans.size)))
        got = jax.jit(jax.vmap(lambda k, c: _randint(k, c, 16, n_max)))(
            keys, jnp.asarray(spans))
        ref = jax.jit(jax.vmap(lambda k, c: jax.random.randint(
            k, (16,), 0, c)))(keys, jnp.asarray(spans))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# ---------------------------------------------------------------------------
# Pallas delta_maxload_rows kernel
# ---------------------------------------------------------------------------


def test_delta_maxload_rows_numpy_parity():
    from repro.kernels import dse_eval
    rng = np.random.default_rng(0)
    for r, m, e in ((1, 1, 4), (3, 5, 48), (8, 32, 224), (4, 130, 60)):
        base = rng.normal(size=(r, e)) * 1e4
        deltas = rng.normal(size=(r, m, e)) * 1e3
        got = np.asarray(dse_eval.delta_maxload_rows(base, deltas,
                                                     interpret=True))
        ref = (base[:, None, :] + deltas).max(axis=-1)
        np.testing.assert_allclose(got, ref, rtol=1e-6)


def test_delta_maxload_rows_weighted_int16_parity():
    """The scheduler's streamed form: int16 flip counts scaled in-kernel.

    ``_scan_solve`` passes small-int flip counts (int16) as ``deltas`` and
    the per-set byte weight as ``weights`` so the f32 [R, M, E] slab is
    never materialized; the link axis streams in ``block_e`` tiles with a
    running max.  Pin all of that against the unfused numpy reference.
    """
    from repro.kernels import dse_eval
    rng = np.random.default_rng(1)
    for r, m, e in ((2, 3, 24), (4, 17, 960), (1, 128, 60)):
        base = (rng.normal(size=(r, e)) * 1e4).astype(np.float32)
        cnt = rng.integers(-2, 3, size=(r, m, e)).astype(np.int16)
        w = rng.uniform(0.5, 8192.0, size=(r, m)).astype(np.float32)
        ref = (base[:, None, :]
               + cnt.astype(np.float32) * w[:, :, None]).max(axis=-1)
        for block_e in (512, 64, 7):   # 7 forces ragged -inf link padding
            got = np.asarray(dse_eval.delta_maxload_rows(
                base, cnt, w, block_e=block_e, interpret=True))
            # in-kernel scale-and-add may fuse to an FMA: 1-ulp tolerance
            np.testing.assert_allclose(got, ref, rtol=1e-6)


# ---------------------------------------------------------------------------
# evaluate_mapping threading: batched prefill == per-layer path
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_mapping():
    from repro.core.hardware import DEFAULT_CONSTRAINTS, HwConfig
    from repro.core.mapper import PimMapper
    from repro.core.workloads import googlenet
    hw = HwConfig.from_tuple((4, 4, 64, 64, 128, 8, 16),
                             cons=DEFAULT_CONSTRAINTS)
    return PimMapper(hw, max_optim_iter=1, lm_cap=20, n_wr=2).map(
        googlenet(1, scale=8))


def test_evaluate_mapping_scan_prefill_matches_serial(tiny_mapping):
    import repro.core.mapper as mapper_mod
    from repro.core.mapper import (_layer_sharing_args, _sched_key,
                                   _sharing_latency, evaluate_mapping)
    hw = tiny_mapping.hw
    _sharing_latency.cache_clear()
    rep = evaluate_mapping(tiny_mapping, seed=2)     # scan + batched prefill
    batch_vals = {}
    for lname in tiny_mapping.choices:
        args = _layer_sharing_args(tiny_mapping, lname)
        key = _sched_key(hw, *args, "ilp", 2, "scan")
        batch_vals[lname] = mapper_mod._SCHED_MEMO.get(key)
        assert batch_vals[lname] is not None
    _sharing_latency.cache_clear()
    for lname in tiny_mapping.choices:   # serial per-layer scan path
        args = _layer_sharing_args(tiny_mapping, lname)
        assert _sharing_latency(hw, *args, "ilp", 2,
                                backend="scan") == batch_vals[lname], lname
    _sharing_latency.cache_clear()
    rep2 = evaluate_mapping(tiny_mapping, seed=2)
    assert rep.latency_s == rep2.latency_s
    assert rep.energy_pj == rep2.energy_pj


def test_evaluate_mapping_backends_both_finite(tiny_mapping):
    from repro.core.mapper import _sharing_latency, evaluate_mapping
    _sharing_latency.cache_clear()
    scan = evaluate_mapping(tiny_mapping, seed=0, scheduler_backend="scan")
    loop = evaluate_mapping(tiny_mapping, seed=0, scheduler_backend="loop")
    for rep in (scan, loop):
        assert np.isfinite(rep.latency_s) and rep.latency_s > 0
        assert np.isfinite(rep.energy_pj) and rep.energy_pj > 0
    # different RNG streams: close, not necessarily equal
    assert scan.latency_s == pytest.approx(loop.latency_s, rel=0.2)


def test_workload_evaluator_scheduler_backend_keys_cache():
    from repro.core.dse import WorkloadEvaluator
    from repro.core.hardware import DEFAULT_CONSTRAINTS, HwConfig
    from repro.core.workloads import googlenet
    hw = HwConfig.from_tuple((4, 4, 64, 64, 128, 8, 16),
                             cons=DEFAULT_CONSTRAINTS)
    wl = [googlenet(1, scale=8)]
    kw = dict(max_optim_iter=1, lm_cap=20, n_wr=2)
    a = WorkloadEvaluator(wl, mapper_kwargs=kw, scheduler_backend="scan")
    b = WorkloadEvaluator(wl, mapper_kwargs=kw, scheduler_backend="loop")
    assert a._content_key(hw) != b._content_key(hw)


def test_initial_cycles_shared_by_backends():
    noc = MeshNoc(4, 4)
    sets = [[0, 1, 2, 3, 4, 5, 6, 7], [8, 9, 10, 11, 12, 13, 14, 15]]
    for r in range(3):   # the deterministic restarts
        a = _initial_cycles(noc, sets, r, random.Random(0))
        b = _initial_cycles(noc, sets, r, random.Random(0))
        assert a == b
        for init, s in zip(a, sets):
            assert sorted(init) == sorted(s)
