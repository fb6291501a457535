"""Batched (vmapped/jitted) reimplementation of ``core.costmodel``.

``batch_part_cost`` scores a ``[N configs] x [L part-layers]`` grid through
the analytic tiling/DRAM/compute model in one JAX call instead of ``N * L``
scalar Python calls.  The computation mirrors ``costmodel.part_layer_cost``
operation-for-operation in float64 (:func:`repro.runtime.x64`), so the
batched result matches the scalar reference within 1e-6 relative tolerance —
including the chosen tiling and loop order — which the engine tests enforce.

Host-side preprocessing packs one row of layer dims and DRAM-layout fields
per part-layer; the program derives from those dims the same power-of-two
tiling candidate grid the scalar model searches (padded to a common ``T``
and masked past each row's grid), so no ``[L, T]`` array crosses to the
device.  The per-candidate ``max(compute, dram)`` bottleneck and the masked
first-argmin over candidates are one f64 ``jnp`` reduction, the same
expression on every backend (Mosaic compiles no f64 Pallas kernel).

Batch axes:
  * configs vary ``pea_row/pea_col``, the three buffer sizes, and the
    DRAM port geometry (``burst_words`` / ``row_words``) — everything a
    Fig. 9 sweep explores;
  * part-layers vary the full conv loop nest plus the in/out
    :class:`~repro.core.layout.DataLayout`.

All configs in one batch must share the same :class:`PimConstraints`
(true for any single DSE campaign).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import lru_cache, partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.costmodel import MAC_ENERGY_PJ, PartCost, _sram_pj_per_bit
from ..core.hardware import HwConfig
from ..core.ir import Layer
from ..core.layout import DataLayout
from ..obs import trace
from ..obs.trace import traced
from ..runtime import x64

INF = float("inf")


@dataclass(frozen=True)
class PartSpec:
    """One row of the layer axis: a part-layer plus its DRAM layouts."""

    layer: Layer
    dl_in: DataLayout
    dl_out: DataLayout


# ---------------------------------------------------------------------------
# Host-side preprocessing
# ---------------------------------------------------------------------------


#: the axes of the candidate tiling grid, in ``part_layer_cost``'s meshgrid
#: order, each with its ``_tile_candidates`` cap (``Q`` has one candidate,
#: ``Q`` itself, when ``Q <= 64``)
_GRID_AXES = (("B", 4), ("K", 7), ("C", 7), ("P", 7), ("Q", 4))


def _n_candidates(dim: int, cap: int) -> int:
    """``len(_tile_candidates(dim, cap))`` in closed form."""
    return min((dim - 1).bit_length() + 1, cap)


def _grid_size(layer: Layer) -> int:
    """Size of ``part_layer_cost``'s candidate tiling grid, in closed form."""
    q = 1 if layer.Q <= 64 else _n_candidates(layer.Q, 4)
    return (_n_candidates(layer.B, 4) * _n_candidates(layer.K, 7)
            * _n_candidates(layer.C, 7) * _n_candidates(layer.P, 7) * q)


def _dl_fields(dl: DataLayout, channels: int) -> tuple[bool, int, int]:
    """(is_bhwc, effective group, alignment) for a fmap with ``channels``."""
    if dl.order == "BHWC":
        return True, channels, channels
    g = min(max(1, dl.group), channels)
    return False, g, g


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


_INT_KEYS = ("B", "C", "H", "W", "K", "HK", "WK", "stride", "P", "Q",
             "in_g", "in_align", "out_g", "out_align")
_FLAG_KEYS = ("heavy", "in_bhwc", "out_bhwc")
_FLOAT_KEYS = ("macs", "w_vals", "i_vals", "o_vals")


@lru_cache(maxsize=65536)
def _spec_static(layer: Layer):
    """The DL-independent row of one part-layer (mapper sweeps repeat them)."""
    ints = tuple(getattr(layer, k) for k in _INT_KEYS[:10])
    floats = (float(layer.macs), float(layer.weight_count),
              float(layer.B * layer.C * layer.H * layer.W),
              float(layer.B * layer.K * layer.P * layer.Q))
    return _grid_size(layer), ints, layer.is_heavy, floats


def _t_bucket(layer: Layer) -> int:
    """The candidate-axis bucket of a part-layer (floor 128, power of two).

    Per-spec, so a spec always lands in the same ``T`` program whatever
    batch it arrives in; padding tiny grids up is cheaper than another
    dispatch round-trip.
    """
    return _next_pow2(max(128, _spec_static(layer)[0]))


def _prep_specs(specs: Sequence[PartSpec], *, t_pad: int | None = None):
    """Pack L part-layer specs into ``[L]`` numpy arrays, one per field.

    Returns ``(lay, t)``: the per-row layer and layout fields, and the
    candidate-axis width ``t`` for ``_batch_cost``, which derives each
    row's tiling grid from its dims on the device.  ``t_pad`` fixes that
    width to a caller-chosen bucket (padding is masked invalid) so
    spec-chunked callers compile one XLA program per ``(L, T-bucket)``
    pair instead of one per distinct tiling-grid size.
    """
    statics = [_spec_static(s.layer) for s in specs]
    t_max = max(st[0] for st in statics)
    if t_pad is not None:
        assert t_pad >= t_max, "t_pad below the largest candidate grid"
        t_max = t_pad
    int_rows, flag_rows, float_rows = [], [], []
    for s, (_, ints, heavy, floats) in zip(specs, statics):
        in_bhwc, gi, ali = _dl_fields(s.dl_in, s.layer.C)
        out_bhwc, go, alo = _dl_fields(s.dl_out, s.layer.K)
        int_rows.append(ints + (gi, ali, go, alo))
        flag_rows.append((heavy, in_bhwc, out_bhwc))
        float_rows.append(floats)
    int_arr = np.array(int_rows, dtype=np.int64)
    flag_arr = np.array(flag_rows, dtype=bool)
    float_arr = np.array(float_rows, dtype=np.float64)
    ints = {k: np.ascontiguousarray(int_arr[:, j])
            for j, k in enumerate(_INT_KEYS)}
    flags = {k: np.ascontiguousarray(flag_arr[:, j])
             for j, k in enumerate(_FLAG_KEYS)}
    floats = {k: np.ascontiguousarray(float_arr[:, j])
              for j, k in enumerate(_FLOAT_KEYS)}
    return {**ints, **flags, **floats}, t_max


def _prep_configs(configs: Sequence[HwConfig]):
    cons = configs[0].cons
    # dedupe first: paired pair-lists repeat each config once per spec, so
    # the per-config field extraction must not scale with the pair count
    uniq: dict[HwConfig, int] = {}
    idx = np.empty(len(configs), dtype=np.intp)
    for i, c in enumerate(configs):
        j = uniq.get(c)
        if j is None:
            if c.cons != cons:
                raise ValueError(
                    "all configs in a batch must share PimConstraints")
            j = uniq[c] = len(uniq)
        idx[i] = j
    n = len(uniq)
    out = {k: np.zeros(n, dtype=np.int64) for k in
           ("pea_row", "pea_col", "ibuf_kib", "wbuf_kib", "obuf_kib",
            "burst_words", "row_words", "width_bits")}
    sram = {k: np.zeros(n, dtype=np.float64) for k in
            ("sram_i", "sram_w", "sram_o")}
    dbytes = cons.data_bits // 8
    for c, i in uniq.items():
        out["pea_row"][i] = c.pea_row
        out["pea_col"][i] = c.pea_col
        out["ibuf_kib"][i] = c.ibuf_kib
        out["wbuf_kib"][i] = c.wbuf_kib
        out["obuf_kib"][i] = c.obuf_kib
        bw = max(1, c.node_dram_width_bits // cons.data_bits)
        out["burst_words"][i] = bw
        out["row_words"][i] = max(
            bw, cons.dram_row_bytes * c.banks_per_node // dbytes)
        out["width_bits"][i] = c.node_dram_width_bits
        sram["sram_i"][i] = _sram_pj_per_bit(c.ibuf_kib)
        sram["sram_w"][i] = _sram_pj_per_bit(c.wbuf_kib)
        sram["sram_o"][i] = _sram_pj_per_bit(c.obuf_kib)
    gathered = {k: v[idx] for k, v in {**out, **sram}.items()}
    return gathered, cons


# ---------------------------------------------------------------------------
# The jitted [N, L, T] cost pipeline
# ---------------------------------------------------------------------------


def _mean_bursts(run, align, burst):
    """JAX port of ``layout.mean_bursts`` (closed form, identical math)."""
    g = jnp.gcd(jnp.maximum(align, 1), burst)
    m = (burst // g).astype(run.dtype)
    burst_f = burst.astype(run.dtype)
    g_f = g.astype(run.dtype)
    q = jnp.ceil(run / burst_f) - 1.0
    r = run - q * burst_f
    over = m - 1.0 - jnp.floor((burst_f - r) / g_f)
    return q + 1.0 + over / m


def _access_cost(fmap, tb, tc, th, tw, is_bhwc, group, align,
                 burst, row_words):
    """JAX port of ``layout.tile_cost_vec`` covering both orders via select.

    ``fmap`` is ``(B, C, H, W)`` as f64 arrays broadcastable against the tile
    arrays; ``is_bhwc/group/align`` are per-layer, ``burst/row_words`` per
    config.
    """
    B, C, H, W = fmap
    tb = jnp.minimum(tb, B)
    tc = jnp.minimum(tc, C)
    th = jnp.minimum(th, H)
    tw = jnp.minimum(tw, W)
    full_w = tw >= W
    full_h = th >= H
    full_c = tc >= C

    # ---- BHWC: linear index ((b*H + h)*W + w)*C + c ------------------------
    run_p = jnp.where(full_c, tw * C, tc)
    nruns_p = jnp.where(full_c, tb * th, tb * th * tw)
    run_p = jnp.where(full_c & full_w, th * W * C, run_p)
    nruns_p = jnp.where(full_c & full_w, tb, nruns_p)
    whole_p = full_c & full_w & full_h
    run_p = jnp.where(whole_p, tb * H * W * C, run_p)
    nruns_p = jnp.where(whole_p, 1.0, nruns_p)
    span_p = jnp.where(whole_p, tb * H * W * C, ((th - 1) * W + tw) * C)
    next_p = jnp.where(whole_p, 1.0, tb)

    # ---- BCHW[Cg]: linear index (((b*(C/g) + cg)*H + h)*W + w)*g + c -------
    g = group
    c_groups = jnp.ceil(tc / g)
    run_c = tw * g * jnp.ones_like(tc)
    nruns_c = tb * c_groups * th
    run_c = jnp.where(full_w, tw * g * th, run_c)
    nruns_c = jnp.where(full_w, tb * c_groups, nruns_c)
    plane = full_w & full_h
    run_c = jnp.where(plane, H * W * g * c_groups, run_c)
    nruns_c = jnp.where(plane, tb, nruns_c)
    whole = plane & full_c
    run_c = jnp.where(whole, tb * C * H * W, run_c)
    nruns_c = jnp.where(whole, 1.0, nruns_c)
    span_c = jnp.where(plane, run_c, ((th - 1) * W + tw) * g)
    next_c = jnp.where(plane, nruns_c, tb * c_groups)

    run = jnp.where(is_bhwc, run_p, run_c)
    n_runs = jnp.where(is_bhwc, nruns_p, nruns_c)
    span = jnp.where(is_bhwc, span_p, span_c)
    n_extents = jnp.where(is_bhwc, next_p, next_c)

    bursts = n_runs * _mean_bursts(run, align, burst)
    rows = n_extents * jnp.maximum(1.0, span / row_words)
    return bursts, rows


def _tile_grid(lay, t: int):
    """Each row's candidate tiling grid, derived from its dims: [L, t].

    Row ``l`` holds ``_tile_candidates`` of its ``B, K, C, P, Q`` in
    ``part_layer_cost``'s order (``np.meshgrid(..., indexing="ij")``
    flattened in C order): index ``i`` splits into mixed-radix digits over
    the per-axis candidate counts, ``Q`` fastest.  Candidate ``j`` of a dim
    ``d`` with ``n = (d - 1).bit_length()`` powers of two below it is
    ``2 ** (j + off)`` while ``j + off < n``, else ``d``, where ``off``
    drops the smallest beyond the cap.

    Returns the int64 tile arrays ``tb, tk, tc, tp, tq`` and the input
    window ``th, tw``, the mask ``valid`` (``i`` below the row's grid
    size) and ``fallback`` ([L, 1]): the scalar model's choice when no
    tiling fits, the first smallest input tile of the grid.
    """
    i32 = jnp.int32
    n, count = {}, {}
    for name, cap in _GRID_AXES:
        n[name] = 32 - jax.lax.clz(lay[name].astype(i32) - 1)
        count[name] = jnp.minimum(n[name] + 1, cap)
    count["Q"] = jnp.where(lay["Q"] <= 64, 1, count["Q"])
    place, size = {}, jnp.ones_like(count["B"])
    for name, _ in reversed(_GRID_AXES):
        place[name], size = size, size * count[name]
    rem = jnp.arange(t, dtype=i32)[None, :]
    g = {}
    for name, cap in _GRID_AXES:
        # a digit is below its cap, so comparisons find it: an integer
        # division compiles to a long sequence for the TPU
        p = place[name][:, None]
        j = sum((rem >= m * p).astype(i32) for m in range(1, cap))
        rem = rem - j * p
        k = j + (n[name] + 1 - count[name])[:, None]
        g["t" + name.lower()] = jnp.where(k < n[name][:, None],
                                          jnp.left_shift(1, k),
                                          lay[name][:, None])
    stride = lay["stride"][:, None]
    g["th"] = (g["tp"] - 1) * stride + lay["HK"][:, None]
    g["tw"] = (g["tq"] - 1) * stride + lay["WK"][:, None]
    g["valid"] = jnp.arange(t, dtype=i32)[None, :] < size[:, None]
    in_tile = jnp.where(g["valid"], g["tb"] * g["tc"] * g["th"] * g["tw"],
                        jnp.iinfo(jnp.int64).max)
    g["fallback"] = jnp.argmin(in_tile, axis=-1, keepdims=True)
    # materialized once, as host inputs would be: fused into each consumer
    # instead, the integer work makes the v5e compile half as long again
    return jax.lax.optimization_barrier(g)


@partial(jax.jit, static_argnames=("t_pad", "data_bits", "psum_bits",
                                   "dram_row_miss", "paired"))
def _batch_cost(cfg, lay, *, t_pad: int, data_bits: int, psum_bits: int,
                dram_row_miss: int, paired: bool = False):
    """Score every (config, part-layer, candidate-tiling) point.

    ``cfg`` arrays are [N] and ``lay`` arrays [L], one row of dims and
    layout fields per part-layer; the candidate tiling grid is derived
    here from the dims (:func:`_tile_grid`), ``t_pad`` candidates wide.
    Returns per-(config, layer) selections, all [N, L].

    ``paired=True`` aligns the config axis WITH the layer axis (``cfg``
    arrays are [L], one config per part-layer): the result is the [1, L]
    diagonal of the grid, costing exactly the requested pairs instead of the
    full cross product — the multi-config mapper sweep, where every config
    brings its own mostly-disjoint spec set.
    """
    f64 = jnp.float64

    def c3(name):  # config axis -> [N, 1, 1]; paired: [1, L, 1]
        v = cfg[name]
        return v[None, :, None] if paired else v[:, None, None]

    def c2(name):  # config axis -> [N, 1]; paired: [1, L]
        v = cfg[name]
        return v[None, :] if paired else v[:, None]

    def l3(name):  # layer axis -> [1, L, 1]
        return lay[name][None, :, None]

    dbytes = data_bits // 8
    pbytes = psum_bits // 8

    g = {k: v[None] for k, v in _tile_grid(lay, t_pad).items()}  # [1, L, T]
    TB, TK, TC, TP, TQ = g["tb"], g["tk"], g["tc"], g["tp"], g["tq"]
    TH, TW = g["th"], g["tw"]
    HK, WK = l3("HK"), l3("WK")

    # ---- capacity filter (int64, exactly as the scalar model) --------------
    fits = ((TB * TC * TH * TW * dbytes * 2 <= c3("ibuf_kib") * 1024)
            & (TK * TC * HK * WK * dbytes * 2 <= c3("wbuf_kib") * 1024)
            & (TB * TK * TP * TQ * pbytes <= c3("obuf_kib") * 1024))
    eligible = fits & g["valid"]
    any_fit = eligible.any(axis=-1, keepdims=True)
    onehot = jnp.arange(t_pad)[None, None, :] == g["fallback"]
    mask = jnp.where(any_fit, eligible, onehot)

    # ---- float views -------------------------------------------------------
    TBf, TKf, TCf = TB.astype(f64), TK.astype(f64), TC.astype(f64)
    TPf, TQf = TP.astype(f64), TQ.astype(f64)
    THf, TWf = TH.astype(f64), TW.astype(f64)
    B, C, H, W = [l3(k).astype(f64) for k in ("B", "C", "H", "W")]
    K, P, Q = [l3(k).astype(f64) for k in ("K", "P", "Q")]
    HKf, WKf = HK.astype(f64), WK.astype(f64)

    n_k = jnp.ceil(K / TKf)
    n_c = jnp.ceil(C / TCf)
    n_bpq = jnp.ceil(B / TBf) * jnp.ceil(P / TPf) * jnp.ceil(Q / TQf)
    n_tiles_i = jnp.ceil(B / TBf) * n_c * jnp.ceil(P / TPf) * jnp.ceil(Q / TQf)
    n_tiles_o = jnp.ceil(B / TBf) * n_k * jnp.ceil(P / TPf) * jnp.ceil(Q / TQf)

    # ---- compute cycles ----------------------------------------------------
    pea_row = c3("pea_row").astype(f64)
    pea_col = c3("pea_col").astype(f64)
    cyc_tile = (jnp.ceil(TCf / pea_row) * jnp.ceil(TKf / pea_col)
                * HKf * WKf * TPf * TQf * TBf)
    compute_cycles = cyc_tile * n_k * n_c * n_bpq

    # ---- DRAM traffic under the two loop orders ----------------------------
    burst = c3("burst_words")
    row_words = c3("row_words").astype(f64)
    ib, ir = _access_cost((B, C, H, W), TBf, TCf, THf, TWf,
                          l3("in_bhwc"), l3("in_g").astype(f64),
                          l3("in_align"), burst, row_words)
    ob, orow = _access_cost((B, K, P, Q), TBf, TKf, TPf, TQf,
                            l3("out_bhwc"), l3("out_g").astype(f64),
                            l3("out_align"), burst, row_words)
    w_vals = l3("w_vals")
    w_bursts = jnp.ceil(w_vals / burst.astype(f64))
    w_rows = jnp.maximum(1.0, w_vals / row_words)

    all_w_fit = (l3("K") * l3("C") * HK * WK * dbytes * 2
                 <= c3("wbuf_kib") * 1024)
    all_i_fit = (l3("B") * l3("C") * l3("H") * l3("W") * dbytes * 2
                 <= c3("ibuf_kib") * 1024)
    i_passes_ko = jnp.where(all_i_fit, 1.0, n_k)
    i_passes_bo = jnp.ones_like(n_k)
    w_passes_ko = jnp.ones_like(n_bpq)
    w_passes_bo = jnp.where(all_w_fit, 1.0, n_bpq)

    i_vals, o_vals = l3("i_vals"), l3("o_vals")

    def dram_terms(i_passes, w_passes):
        bursts = (ib * n_tiles_i * i_passes + w_bursts * w_passes
                  + ob * n_tiles_o)
        rows = (ir * n_tiles_i * i_passes + w_rows * w_passes
                + orow * n_tiles_o)
        values = i_vals * i_passes + w_vals * w_passes + o_vals
        return bursts, rows, values

    b_ko, r_ko, v_ko = dram_terms(i_passes_ko, w_passes_ko)
    b_bo, r_bo, v_bo = dram_terms(i_passes_bo, w_passes_bo)
    dram_cycles_ko = b_ko + r_ko * dram_row_miss
    dram_cycles_bo = b_bo + r_bo * dram_row_miss
    use_bo = dram_cycles_bo < dram_cycles_ko
    dram_cycles = jnp.where(use_bo, dram_cycles_bo, dram_cycles_ko)
    bursts = jnp.where(use_bo, b_bo, b_ko)
    rows = jnp.where(use_bo, r_bo, r_ko)
    values = jnp.where(use_bo, v_bo, v_ko)

    # ---- inner reduction: bottleneck + masked first-argmin ----------------
    n, l_dim = compute_cycles.shape[0], compute_cycles.shape[1]
    shape3 = (n, l_dim, t_pad)
    cand = jnp.where(mask, jnp.maximum(compute_cycles, dram_cycles), jnp.inf)
    total = jnp.min(cand, axis=-1)
    # first occurrence of the min, matching np.argmin in the scalar model
    best = jnp.argmin(cand, axis=-1).astype(jnp.int32)

    def pick(arr):
        full = jnp.broadcast_to(arr, shape3)
        return jnp.take_along_axis(full, best[:, :, None], axis=-1)[:, :, 0]

    def pick_tile(arr):  # config-independent [1, L, T]: cheap [L, T] gather
        return arr[0][jnp.arange(l_dim)[None, :], best]

    tb_, tk_, tc_ = pick_tile(TB), pick_tile(TK), pick_tile(TC)
    tp_, tq_ = pick_tile(TP), pick_tile(TQ)
    compute_best = pick(compute_cycles)
    dram_best = pick(dram_cycles)
    bursts_best = pick(bursts)
    rows_best = pick(rows)
    values_best = pick(values)
    use_bo_best = pick(use_bo)

    # ---- energies at the chosen tiling -------------------------------------
    macs = lay["macs"][None, :]
    e_mac = macs * MAC_ENERGY_PJ
    pea_row2 = c2("pea_row")
    pea_col2 = c2("pea_col")
    ibuf_reads = macs / jnp.maximum(1, jnp.minimum(tk_, pea_col2)).astype(f64)
    wbuf_reads = macs / jnp.maximum(1, tb_ * tp_ * tq_).astype(f64)
    obuf_acc = 2.0 * macs / jnp.maximum(
        1, jnp.minimum(tc_, pea_row2)).astype(f64)
    e_sram = (ibuf_reads * data_bits * c2("sram_i")
              + wbuf_reads * data_bits * c2("sram_w")
              + obuf_acc * psum_bits * c2("sram_o"))

    width_bits = c2("width_bits").astype(f64)
    moved_bits = bursts_best * width_bits
    useful_bits = values_best * data_bits
    heavy = lay["heavy"][None, :]

    out = {
        "total_cycles": total,
        "compute_cycles": compute_best,
        "dram_cycles": dram_best,
        "dram_values": values_best,
        "rows": rows_best,
        "moved_bits": moved_bits,
        "useful_bits": useful_bits,
        "e_mac": e_mac,
        "e_sram": e_sram,
        "use_bo": use_bo_best,
        "tb": tb_, "tk": tk_, "tc": tc_, "tp": tp_, "tq": tq_,
    }
    zero = jnp.zeros_like(total)
    for k in ("total_cycles", "compute_cycles", "dram_cycles", "dram_values",
              "rows", "moved_bits", "useful_bits", "e_mac", "e_sram"):
        out[k] = jnp.where(heavy, out[k], zero)
    for k in ("tb", "tk", "tc", "tp", "tq"):
        out[k] = jnp.where(heavy, out[k], 1)
    out["use_bo"] = jnp.where(heavy, out["use_bo"], False)
    return out


#: module-level jit objects, keyed for ``compiled_program_count``-style
#: introspection (see :func:`repro.engine.engine_program_counts`)
_JITTED = {
    "batch_cost": _batch_cost,
}


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


# array fields of BatchCostResult, in merge order — shared by the grid
# (batch_part_cost) and paired (batch_part_cost_paired) block/bucket
# merge scaffolding so the two paths cannot drift apart
_RESULT_FIELDS = ("latency_s", "energy_pj", "compute_s", "dram_s",
                  "dram_bytes", "e_mac_pj", "e_sram_pj", "e_dram_pj",
                  "tiling", "use_bpq_outer")


@dataclass
class BatchCostResult:
    """Per-(config, part-layer) costs; every array is ``[N, L]``."""

    configs: list[HwConfig]
    specs: list[PartSpec]
    latency_s: np.ndarray
    energy_pj: np.ndarray
    compute_s: np.ndarray
    dram_s: np.ndarray
    dram_bytes: np.ndarray
    e_mac_pj: np.ndarray
    e_sram_pj: np.ndarray
    e_dram_pj: np.ndarray
    tiling: np.ndarray           # [N, L, 5] int
    use_bpq_outer: np.ndarray    # [N, L] bool

    def part_cost(self, i: int, j: int) -> PartCost:
        """Reconstruct the scalar :class:`PartCost` view of one cell."""
        return PartCost(
            latency_s=float(self.latency_s[i, j]),
            energy_pj=float(self.energy_pj[i, j]),
            compute_s=float(self.compute_s[i, j]),
            dram_s=float(self.dram_s[i, j]),
            dram_bytes=float(self.dram_bytes[i, j]),
            e_mac_pj=float(self.e_mac_pj[i, j]),
            e_sram_pj=float(self.e_sram_pj[i, j]),
            e_dram_pj=float(self.e_dram_pj[i, j]),
            tiling=tuple(int(v) for v in self.tiling[i, j]),
            loop_order="BPQ_outer" if self.use_bpq_outer[i, j] else "K_outer",
        )


@traced("batch_cost", argspec=lambda configs, specs, **kw:
        {"configs": len(configs), "specs": len(specs)})
def batch_part_cost(configs: Sequence[HwConfig],
                    specs: Sequence[PartSpec | tuple],
                    *, chunk: int = 32, spec_chunk: int | None = None
                    ) -> BatchCostResult:
    """Score ``[len(configs), len(specs)]`` part-layer costs in one pipeline.

    ``chunk`` bounds the config-axis block handed to one jit call (the
    candidate axis is materialized per block, so memory scales with
    ``chunk * L * T``).  Configs are padded to a full final chunk so XLA
    compiles exactly one program per (L, T, chunk) shape.

    ``spec_chunk`` additionally blocks the *spec* axis — the mapper's
    candidate sweeps batch thousands of part-layers against one config, so
    memory must scale with ``spec_chunk * T`` instead of ``L * T``.  Blocks
    are padded to a full ``spec_chunk`` (repeating the last spec) and the
    candidate axis is bucketed to a power of two, bounding XLA compiles to
    one program per (spec_chunk, T-bucket) pair.
    """
    specs = [s if isinstance(s, PartSpec) else PartSpec(*s) for s in specs]
    if not configs or not specs:
        raise ValueError("need at least one config and one spec")
    fields = _RESULT_FIELDS
    t_pad = None
    if spec_chunk is not None:
        # group by candidate-axis bucket first: a mixed batch otherwise pads
        # every small tiling grid to the largest one in the batch
        buckets = {}
        for i, s in enumerate(specs):
            buckets.setdefault(_t_bucket(s.layer), []).append(i)
        t_pad = max(buckets)
        if len(buckets) > 1:
            merged: dict[str, np.ndarray] = {}
            for tb in sorted(buckets):
                idxs = buckets[tb]
                sub = batch_part_cost(configs, [specs[i] for i in idxs],
                                      chunk=chunk, spec_chunk=spec_chunk)
                for f in fields:
                    v = getattr(sub, f)
                    if f not in merged:
                        merged[f] = np.zeros((v.shape[0], len(specs))
                                             + v.shape[2:], v.dtype)
                    merged[f][:, idxs] = v
            return BatchCostResult(configs=list(configs), specs=specs,
                                   **merged)
    if spec_chunk is not None and len(specs) > spec_chunk:
        blocks = []
        for s in range(0, len(specs), spec_chunk):
            block = specs[s:s + spec_chunk]
            n_real = len(block)
            block = block + [block[-1]] * (spec_chunk - n_real)
            res = batch_part_cost(configs, block, chunk=chunk,
                                  spec_chunk=spec_chunk)
            blocks.append((res, n_real))
        merged = {f: np.concatenate([getattr(r, f)[:, :n] for r, n in blocks],
                                    axis=1) for f in fields}
        return BatchCostResult(configs=list(configs), specs=specs, **merged)
    lay_np, t = _prep_specs(specs, t_pad=t_pad)
    cfg_np, cons = _prep_configs(configs)

    n = len(configs)
    chunk = max(1, min(chunk, n))
    pad = (-n) % chunk
    if pad:
        cfg_np = {k: np.concatenate([v, np.repeat(v[-1:], pad, axis=0)])
                  for k, v in cfg_np.items()}

    outs: dict[str, list[np.ndarray]] = {}
    with x64():
        lay = {k: jnp.asarray(v) for k, v in lay_np.items()}
        for s in range(0, n + pad, chunk):
            cfg = {k: jnp.asarray(v[s:s + chunk]) for k, v in cfg_np.items()}
            res = _batch_cost(cfg, lay, t_pad=t, data_bits=cons.data_bits,
                              psum_bits=cons.psum_bits,
                              dram_row_miss=cons.dram_row_miss_cycles)
            with trace.span("device_wait", cat="engine", what="batch_cost"):
                for k, v in res.items():
                    # this per-chunk pull IS the dispatch boundary: chunks
                    # must land on host to be concatenated, and each pull
                    # overlaps the next chunk's dispatch
                    # pimlint: disable-next-line=host-sync -- sanctioned per-chunk boundary pull
                    outs.setdefault(k, []).append(np.asarray(v))
    res = {k: np.concatenate(v, axis=0)[:n] for k, v in outs.items()}
    return _finalize_result(res, configs, specs, cons)


def _finalize_result(res: dict, configs, specs, cons) -> BatchCostResult:
    """Host-side energies/units for raw ``_batch_cost`` outputs ([N, L])."""
    freq = cons.freq_hz
    dbytes = cons.data_bits // 8
    e_dram = (np.maximum(res["moved_bits"], res["useful_bits"])
              * cons.dram_energy_pj_per_bit
              + res["rows"] * cons.dram_row_act_energy_pj)
    heavy = np.array([s.layer.is_heavy for s in specs])[None, :]
    e_dram = np.where(heavy, e_dram, 0.0)
    tiling = np.stack([res["tb"], res["tk"], res["tc"], res["tp"], res["tq"]],
                      axis=-1)
    e_mac = np.broadcast_to(res["e_mac"], res["total_cycles"].shape)
    return BatchCostResult(
        configs=list(configs), specs=specs,
        latency_s=res["total_cycles"] / freq,
        energy_pj=e_mac + res["e_sram"] + e_dram,
        compute_s=res["compute_cycles"] / freq,
        dram_s=res["dram_cycles"] / freq,
        dram_bytes=res["dram_values"] * dbytes,
        e_mac_pj=e_mac,
        e_sram_pj=res["e_sram"],
        e_dram_pj=e_dram,
        tiling=tiling,
        use_bpq_outer=res["use_bo"].astype(bool),
    )


@traced("batch_cost", argspec=lambda configs, specs, **kw:
        {"pairs": len(specs), "mode": "paired"})
def batch_part_cost_paired(configs: Sequence[HwConfig],
                           specs: Sequence[PartSpec | tuple],
                           *, spec_chunk: int = 1024) -> BatchCostResult:
    """Score aligned ``(config, part-layer)`` PAIRS: cell ``j`` costs
    ``specs[j]`` on ``configs[j]``.

    The multi-config mapper sweep batches many configs whose candidate spec
    sets are mostly disjoint (region shapes follow each config's node-array
    geometry); the ``[N, L]`` grid of :func:`batch_part_cost` would compute —
    and pay for — the full cross product.  Here the config fields ride the
    spec axis instead ([L] arrays broadcast per pair), so compute scales with
    the number of requested pairs, exactly like the per-config calls it
    replaces, while keeping one fused engine dispatch.

    Pair blocks are chunked to ``spec_chunk`` and padded to power-of-two
    lengths (floor 128, repeating the last pair), and the candidate axis is
    bucketed like the spec-chunked grid path, so XLA compiles one program per
    (pair-bucket, T-bucket) shape instead of one per distinct pair count.
    Result arrays are ``[1, L]`` (``res.latency_s[0][j]`` etc.); every config
    must share one :class:`PimConstraints`.  Values match the corresponding
    ``batch_part_cost([cfg], [spec])`` cells exactly — the operations are the
    same elementwise float64 pipeline.
    """
    specs = [s if isinstance(s, PartSpec) else PartSpec(*s) for s in specs]
    configs = list(configs)
    if len(configs) != len(specs):
        raise ValueError("paired costing needs len(configs) == len(specs)")
    if not specs:
        raise ValueError("need at least one (config, spec) pair")
    # same per-spec T-bucket key as batch_part_cost's spec-chunked path
    buckets: dict[int, list[int]] = {}
    for i, s in enumerate(specs):
        buckets.setdefault(_t_bucket(s.layer), []).append(i)
    if len(buckets) > 1:
        merged: dict[str, np.ndarray] = {}
        for tb in sorted(buckets):
            idxs = buckets[tb]
            sub = batch_part_cost_paired([configs[i] for i in idxs],
                                         [specs[i] for i in idxs],
                                         spec_chunk=spec_chunk)
            for f in _RESULT_FIELDS:
                v = getattr(sub, f)
                if f not in merged:
                    merged[f] = np.zeros((1, len(specs)) + v.shape[2:],
                                         v.dtype)
                merged[f][:, idxs] = v
        return BatchCostResult(configs=configs, specs=specs, **merged)
    t_pad = max(buckets)
    if len(specs) > spec_chunk:
        blocks = []
        for s in range(0, len(specs), spec_chunk):
            blocks.append(batch_part_cost_paired(
                configs[s:s + spec_chunk], specs[s:s + spec_chunk],
                spec_chunk=spec_chunk))
        merged = {f: np.concatenate([getattr(b, f) for b in blocks], axis=1)
                  for f in _RESULT_FIELDS}
        return BatchCostResult(configs=configs, specs=specs, **merged)
    n_real = len(specs)
    n_pad = min(spec_chunk, _next_pow2(max(128, n_real)))
    if n_pad > n_real:  # pow2 pair-bucket: bounded XLA program count
        configs = configs + [configs[-1]] * (n_pad - n_real)
        specs = specs + [specs[-1]] * (n_pad - n_real)
    lay_np, t = _prep_specs(specs, t_pad=t_pad)
    cfg_np, cons = _prep_configs(configs)
    with x64():
        lay = {k: jnp.asarray(v) for k, v in lay_np.items()}
        cfg = {k: jnp.asarray(v) for k, v in cfg_np.items()}
        res = _batch_cost(cfg, lay, t_pad=t, data_bits=cons.data_bits,
                          psum_bits=cons.psum_bits,
                          dram_row_miss=cons.dram_row_miss_cycles,
                          paired=True)
    with trace.span("device_wait", cat="engine", what="batch_cost"):
        res = {k: np.asarray(v)[:, :n_real] for k, v in res.items()}
    return _finalize_result(res, configs[:n_real], specs[:n_real], cons)


def batch_area_mm2(configs: Sequence[HwConfig]) -> np.ndarray:
    """Vectorized ``HwConfig.area_mm2`` for a whole proposal batch."""
    if not configs:
        return np.zeros(0)
    cons = configs[0].cons
    t = np.array([c.as_tuple() for c in configs], dtype=np.float64)
    na = t[:, 0] * t[:, 1]
    pe = t[:, 2] * t[:, 3] * cons.mac_area_um2 * 1e-6
    buf_mib = (t[:, 4] + t[:, 5] + t[:, 6]) / 1024
    return na * (pe + buf_mib * cons.sram_area_mm2_per_mib
                 + cons.node_fixed_area_mm2)


def batch_max_link_load(loads: np.ndarray, valid: np.ndarray | None = None
                        ) -> np.ndarray:
    """Max-link-load (Eq. 4) for a batch of candidate schedules.

    ``loads`` is ``[S, E]`` — one row per candidate schedule, one column per
    directed mesh link (``MeshNoc.link_loads`` order); ``valid`` masks
    links out.  A float64 NumPy row max; returns ``[S]``.
    """
    loads = np.asarray(loads, np.float64)
    if valid is not None:
        loads = np.where(valid, loads, -np.inf)
    return loads.max(axis=-1)
