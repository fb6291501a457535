"""Pallas row reductions of the DSE engine.

Two kernels are on the main path, both in float32:

* ``delta_maxload_rows`` — the engine Data-Scheduler's move scoring: fuse
  the ``base + delta`` link-load accumulation of a whole 2-opt proposal
  batch with the per-proposal max-link reduction (one row per search chain,
  one slab per proposed segment reversal).
* ``lcb_rows`` — the PIM-Tuner's fused propose reduction: for every query
  feature row, the pairwise squared distance to the (masked) training
  features, the RBF cross-kernel, the GP posterior mean/variance against a
  precomputed ``K^-1`` / ``K^-1 y``, and the lower-confidence-bound score,
  all in one pass.

Four more have no main-path caller; their tests keep them honest:

* ``tile_select`` — fused ``max(compute, dram)`` + masked first-argmin over
  candidate tilings;
* ``argmin_rows`` / ``minplus_rows`` — the Algorithm-2 knapsack and segment
  min-plus reductions (``PimMapper(dp_reduce="pallas")``);
* ``max_rows`` — row-wise masked max.

Their main-path call sites run in float64 (the engine's 1e-6 parity
contract with the scalar cost model), which Mosaic does not compile, so
those sites use the same reduction as a plain jnp/NumPy expression on
every backend.

Blocks follow the TPU tiling: a block's last two dims are (8, 128)
aligned or whole, so per-row results come out as ``[rows, 1]`` columns.
``delta_maxload_rows`` *streams* the link axis (the innermost grid
dimension walks link tiles with a running max in the revisited output
block).  Kernels compile natively on a TPU backend and run in interpret
mode elsewhere (:func:`repro.runtime.resolve_interpret`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..runtime import resolve_interpret


def _I0():
    """Block index 0 as int32: a bare ``0`` in an index map traces as int64
    under x64 (the scheduler scores moves inside its f64 scope), which
    Mosaic refuses."""
    return jnp.int32(0)


def _tile_select_kernel(c_ref, d_ref, v_ref, tot_ref, idx_ref):
    total = jnp.maximum(c_ref[...], d_ref[...])
    total = jnp.where(v_ref[...], total, jnp.inf)
    tot_ref[...] = jnp.min(total, axis=-1)
    # first occurrence of the min, matching np.argmin in the scalar model
    idx_ref[...] = jnp.argmin(total, axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def _tile_select(compute_cycles, dram_cycles, valid, *, block_r: int,
                 interpret: bool):
    r, t = compute_cycles.shape
    grid = (pl.cdiv(r, block_r),)
    in_spec = pl.BlockSpec((block_r, t), lambda i: (i, 0))
    out_spec = pl.BlockSpec((block_r,), lambda i: (i,))
    return pl.pallas_call(
        _tile_select_kernel,
        grid=grid,
        in_specs=[in_spec, in_spec, in_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((r,), compute_cycles.dtype),
                   jax.ShapeDtypeStruct((r,), jnp.int32)],
        interpret=interpret,
    )(compute_cycles, dram_cycles, valid)


def tile_select(compute_cycles, dram_cycles, valid, *, block_r: int = 8,
                interpret: bool | None = None):
    """``[R, T] -> ([R] total, [R] idx)`` fused max + masked first-argmin.

    Rows with no valid candidate return ``inf`` / index 0 — the caller
    (engine/batch_cost) guarantees at least the fallback tiling is valid.
    """
    interpret = resolve_interpret(interpret)
    r, t = compute_cycles.shape
    block_r = max(1, min(block_r, r))
    pad = (-r) % block_r
    if pad:
        compute_cycles = jnp.pad(compute_cycles, ((0, pad), (0, 0)))
        dram_cycles = jnp.pad(dram_cycles, ((0, pad), (0, 0)))
        valid = jnp.pad(valid, ((0, pad), (0, 0)))
    tot, idx = _tile_select(compute_cycles, dram_cycles, valid,
                            block_r=block_r, interpret=interpret)
    return tot[:r], idx[:r]


def _argmin_rows_kernel(x_ref, v_ref, min_ref, idx_ref):
    x = jnp.where(v_ref[...], x_ref[...], jnp.inf)
    min_ref[...] = jnp.min(x, axis=-1)
    # first occurrence of the min, matching the scalar DP's strict-< update
    idx_ref[...] = jnp.argmin(x, axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def _argmin_rows(x, valid, *, block_r: int, interpret: bool):
    r, t = x.shape
    grid = (pl.cdiv(r, block_r),)
    in_spec = pl.BlockSpec((block_r, t), lambda i: (i, 0))
    out_spec = pl.BlockSpec((block_r,), lambda i: (i,))
    return pl.pallas_call(
        _argmin_rows_kernel,
        grid=grid,
        in_specs=[in_spec, in_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((r,), x.dtype),
                   jax.ShapeDtypeStruct((r,), jnp.int32)],
        interpret=interpret,
    )(x, valid)


def argmin_rows(x, valid=None, *, block_r: int = 128,
                interpret: bool | None = None):
    """``[R, T] -> ([R] min, [R] idx)`` row-wise masked min + first-argmin.

    The Algorithm-2 knapsack inner reduction: one row per capacity cell, one
    column per layer candidate.  Rows with no valid (finite) candidate return
    ``inf`` / index 0; the caller maps those back to "no choice".
    """
    interpret = resolve_interpret(interpret)
    x = jnp.asarray(x)
    if valid is None:
        valid = jnp.ones(x.shape, dtype=bool)
    r, t = x.shape
    block_r = max(1, min(block_r, r))
    pad = (-r) % block_r
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        valid = jnp.pad(valid, ((0, pad), (0, 0)))
    mn, idx = _argmin_rows(x, valid, block_r=block_r, interpret=interpret)
    return mn[:r], idx[:r]


def _minplus_rows_kernel(a_ref, b_ref, min_ref, idx_ref):
    x = a_ref[...][None, :] + b_ref[...]
    min_ref[...] = jnp.min(x, axis=-1)
    # first occurrence of the min, matching the sequential segment DP's
    # strict-< update order (i ascending)
    idx_ref[...] = jnp.argmin(x, axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def _minplus_rows(a, b, *, block_r: int, interpret: bool):
    r, t = b.shape
    grid = (pl.cdiv(r, block_r),)
    return pl.pallas_call(
        _minplus_rows_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((t,), lambda i: (0,)),
                  pl.BlockSpec((block_r, t), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block_r,), lambda i: (i,)),
                   pl.BlockSpec((block_r,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct((r,), b.dtype),
                   jax.ShapeDtypeStruct((r,), jnp.int32)],
        interpret=interpret,
    )(a, b)


def minplus_rows(a, b, *, block_r: int = 128, interpret: bool | None = None):
    """``([T] a, [R, T] b) -> ([R] min, [R] idx)`` fused min-plus reduction.

    Row ``r`` scores ``a + b[r]`` elementwise and reduces with a masked-free
    min + first-argmin — the Algorithm-2 *segment* min-plus convolution: ``a``
    is the running multi-segment DP table, ``b[r]`` the current segment's
    best-perf column reversed/shifted so that column ``i`` holds the segment's
    cost at budget ``r - i`` (``inf`` where ``i > r``).  Rows whose min is
    ``inf`` (no feasible split) return index 0; the caller maps those back to
    "no choice", exactly like :func:`argmin_rows`.
    """
    interpret = resolve_interpret(interpret)
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    r, t = b.shape
    block_r = max(1, min(block_r, r))
    pad = (-r) % block_r
    if pad:
        b = jnp.pad(b, ((0, pad), (0, 0)))
    mn, idx = _minplus_rows(a, b, block_r=block_r, interpret=interpret)
    return mn[:r], idx[:r]


def _lcb_rows_kernel(zq_ref, zt_ref, alpha_ref, kinv_ref, v_ref, par_ref,
                     out_ref):
    zq = zq_ref[...]                                          # [bq, D]
    zt = zt_ref[...]                                          # [N, D]
    d2 = jnp.sum((zq[:, None, :] - zt[None, :, :]) ** 2, -1)  # [bq, N]
    ls2, sf2, beta = par_ref[0], par_ref[1], par_ref[2]
    kq = sf2 * jnp.exp(-0.5 * d2 / ls2)
    # padded training rows contribute nothing: their cross-kernel column is
    # zeroed, and the padded block of kinv is the identity by construction
    kq = jnp.where(v_ref[...], kq, 0.0)                       # v: [1, N]
    mean = jnp.dot(kq, alpha_ref[...],                        # [bq, 1]
                   preferred_element_type=kq.dtype)
    t = jnp.dot(kq, kinv_ref[...], preferred_element_type=kq.dtype)
    var = sf2 - jnp.sum(t * kq, axis=-1, keepdims=True)
    out_ref[...] = mean - beta * jnp.sqrt(jnp.clip(var, 1e-9))


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def _lcb_rows(zq, zt, alpha, kinv, valid, params, *, block_q: int,
              interpret: bool):
    q, d = zq.shape
    n = zt.shape[0]
    grid = (pl.cdiv(q, block_q),)
    # TPU tiling: every block is 2-D with its last two dims either (8, 128)
    # aligned or whole; the per-query result is a [bq, 1] column (a 1-D
    # block would have to match XLA's own tiling of the [Q] array), and the
    # three scalars ride in SMEM
    return pl.pallas_call(
        _lcb_rows_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_q, d), lambda i: (i, _I0())),
                  pl.BlockSpec((n, d), lambda i: (_I0(), _I0())),
                  pl.BlockSpec((n, 1), lambda i: (_I0(), _I0())),
                  pl.BlockSpec((n, n), lambda i: (_I0(), _I0())),
                  pl.BlockSpec((1, n), lambda i: (_I0(), _I0())),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((block_q, 1), lambda i: (i, _I0())),
        out_shape=jax.ShapeDtypeStruct((q, 1), zq.dtype),
        interpret=interpret,
    )(zq, zt, alpha.reshape(n, 1), kinv, valid.reshape(1, n), params)


def lcb_rows(zq, zt, alpha, kinv, valid, ls2, sf2, beta, *,
             block_q: int = 256, interpret: bool | None = None):
    """``([Q,D] zq, [N,D] zt, [N] alpha, [N,N] kinv, [N] valid) -> [Q] lcb``.

    Fused GP-LCB scoring of a candidate batch: pairwise squared distances,
    RBF cross-kernel ``kq = sf2 * exp(-d2 / (2 ls2))``, posterior mean
    ``kq @ alpha`` and variance ``sf2 - kq @ kinv @ kq^T`` (clipped at 1e-9),
    and the lower confidence bound ``mean - beta * sqrt(var)``.  ``alpha`` and
    ``kinv`` are the precomputed ``K^-1 y`` / ``K^-1`` of the (masked)
    training kernel; invalid (padded) training rows are dropped via ``valid``.
    """
    interpret = resolve_interpret(interpret)
    zq = jnp.asarray(zq)
    zt = jnp.asarray(zt)
    params = jnp.stack([jnp.asarray(ls2, zq.dtype), jnp.asarray(sf2, zq.dtype),
                        jnp.asarray(beta, zq.dtype)])
    q = zq.shape[0]
    block_q = max(1, min(block_q, q))
    pad = (-q) % block_q
    if pad:
        zq = jnp.pad(zq, ((0, pad), (0, 0)))
    out = _lcb_rows(zq, zt, jnp.asarray(alpha), jnp.asarray(kinv),
                    jnp.asarray(valid), params, block_q=block_q,
                    interpret=interpret)
    return out[:q, 0]


def _delta_maxload_rows_kernel(b_ref, d_ref, w_ref, o_ref):
    # streaming running-max: the link (E) axis is the innermost grid dim,
    # so the output block is revisited across link tiles — Pallas
    # double-buffers the (base, delta) tile loads while the previous tile
    # reduces, and the full E axis never has to fit in one VMEM block
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref[...], -jnp.inf)
    d = d_ref[...].astype(o_ref.dtype) * w_ref[...]           # [bm, be]
    part = jnp.max(b_ref[...] + d, axis=-1, keepdims=True)    # [bm, 1]
    o_ref[...] = jnp.maximum(o_ref[...], part)


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_e", "interpret"))
def _delta_maxload_rows(base, deltas, weights, *, block_m: int,
                        block_e: int, interpret: bool):
    r, m, e = deltas.shape
    grid = (r, pl.cdiv(m, block_m), pl.cdiv(e, block_e))
    # TPU tiling: the row axis is squeezed (None) and every block's last
    # two dims are (8, 128) aligned or whole — base rides as [R, 1, E],
    # the per-proposal weights and results as [R, M, 1] columns
    out = pl.pallas_call(
        _delta_maxload_rows_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((None, 1, block_e),
                               lambda i, j, k: (i, _I0(), k)),
                  pl.BlockSpec((None, block_m, block_e),
                               lambda i, j, k: (i, j, k)),
                  pl.BlockSpec((None, block_m, 1),
                               lambda i, j, k: (i, j, _I0()))],
        out_specs=pl.BlockSpec((None, block_m, 1),
                               lambda i, j, k: (i, j, _I0())),
        out_shape=jax.ShapeDtypeStruct((r, m, 1), base.dtype),
        interpret=interpret,
    )(base.reshape(r, 1, e), deltas, weights.reshape(r, m, 1))
    return out[:, :, 0]


def delta_maxload_rows(base, deltas, weights=None, *, block_m: int = 128,
                       block_e: int = 512, interpret: bool | None = None):
    """``([R, E] base, [R, M, E] deltas) -> [R, M] max(base + delta)``.

    The engine Data-Scheduler's fused move-scoring reduction: row ``r`` is
    one 2-opt chain's current link loads, ``deltas[r, m]`` the link-load
    delta of its ``m``-th proposed segment reversal, and the output the
    proposal's Eq. 4 objective — the broadcast add and the max-link
    reduction fused in one pass instead of materializing ``base + delta``.

    ``weights [R, M]`` optionally scales each proposal's delta slab
    in-kernel (``base + deltas * w``): the scheduler passes its small-int
    flip *counts* (int16) plus the per-set byte weight, so the f32 ``[R, M,
    E]`` delta tensor is never materialized in memory (XLA may fuse the
    scale-and-add into an FMA, so this path can differ from the unfused
    two-op reference by 1 ulp — scheduler acceptance is protected by its
    exact-f64 gate, never by these scores).  The link axis is
    *streamed*: the grid's innermost dimension walks ``block_e``-wide link
    tiles with a running max in the revisited output block, so the 960-link
    16x16 mesh no longer needs the whole E axis resident per block.
    """
    interpret = resolve_interpret(interpret)
    base = jnp.asarray(base)
    deltas = jnp.asarray(deltas)
    r, m, e = deltas.shape
    if weights is None:
        weights = jnp.ones((r, m), base.dtype)
    weights = jnp.asarray(weights, base.dtype)
    block_m = max(1, min(block_m, m))
    block_e = max(1, min(block_e, e))
    pad_m = (-m) % block_m
    if pad_m:
        deltas = jnp.pad(deltas, ((0, 0), (0, pad_m), (0, 0)))
        weights = jnp.pad(weights, ((0, 0), (0, pad_m)))
    pad_e = (-e) % block_e
    if pad_e:
        # padded links must not win the max: -inf base, zero delta
        base = jnp.pad(base, ((0, 0), (0, pad_e)),
                       constant_values=-jnp.inf)
        deltas = jnp.pad(deltas, ((0, 0), (0, 0), (0, pad_e)))
    out = _delta_maxload_rows(base, deltas, weights, block_m=block_m,
                              block_e=block_e, interpret=interpret)
    return out[:, :m]


def _max_rows_kernel(x_ref, v_ref, o_ref):
    x = jnp.where(v_ref[...], x_ref[...], -jnp.inf)
    o_ref[...] = jnp.max(x, axis=-1)


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def _max_rows(x, valid, *, block_r: int, interpret: bool):
    r, t = x.shape
    grid = (pl.cdiv(r, block_r),)
    in_spec = pl.BlockSpec((block_r, t), lambda i: (i, 0))
    return pl.pallas_call(
        _max_rows_kernel,
        grid=grid,
        in_specs=[in_spec, in_spec],
        out_specs=pl.BlockSpec((block_r,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((r,), x.dtype),
        interpret=interpret,
    )(x, valid)


def max_rows(x, valid=None, *, block_r: int = 8,
             interpret: bool | None = None):
    """Row-wise masked max — the Eq. 4 max-link-load reduction, batched."""
    interpret = resolve_interpret(interpret)
    x = jnp.asarray(x)
    if valid is None:
        valid = jnp.ones(x.shape, dtype=bool)
    r, t = x.shape
    block_r = max(1, min(block_r, r))
    pad = (-r) % block_r
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        valid = jnp.pad(valid, ((0, pad), (0, 0)))
    out = _max_rows(x, valid, block_r=block_r, interpret=interpret)
    return out[:r]
