"""PIM-Mapper (Sec. VI): joint SM / LM / WR / DL optimization for one DNN.

Implements the paper's Algorithm 1: candidate generation per segment (SM via
slicing trees; per layer, WR values from full replication down to 1 with the
best LM searched for each), Algorithm 2's dynamic program to pick one
candidate per segment/layer under the per-node DRAM capacity, and the
alternated DL optimization pass (MAX_OPTIM_ITER iterations).

The DP's ``Perf`` values use fast analytic ring estimates for the
data-sharing traffic (``partition.comm_estimate``); the final chosen mapping
is re-costed with the Data-Scheduler's optimized Hamilton cycles
(:func:`evaluate_mapping`), mirroring the paper's mapper→scheduler split.

:meth:`PimMapper.map_many` maps one DNN under a whole batch of hardware
configs in lockstep, costing every phase's candidate sweep in one
multi-config engine call (``engine.batch_part_cost_paired``) — the DSE
loop's ``evaluate_all_legal`` path maps entire proposal batches through it.
"""

from __future__ import annotations

import math
import threading
from collections import OrderedDict
from dataclasses import dataclass, field
from functools import lru_cache
from typing import Iterable, Iterator, Sequence

from .costmodel import part_layer_cost
from .hardware import HwConfig
from .ir import DnnGraph, Layer, Segment
from .layout import (DataLayout, enumerate_layouts, sequential_access_cost,
                     tile_access_cost)
from .noc import MeshNoc
from .partition import (LM, comm_batch_geometry, comm_estimate,
                        comm_estimate_batch, comm_eval_geometry,
                        enumerate_lms, group_coords, loop_strides, part_layer,
                        wr_candidates, LOOPS)
from .regions import SM, Region, gen_sm_candidates
from .scheduler import solve_ilp_ls, SOLVERS
from ..obs import trace

INF = float("inf")

BACKENDS = ("batched", "scalar")


@dataclass
class LayerChoice:
    lm: LM
    wr: int
    dl_in: DataLayout
    dl_out: DataLayout
    region: Region
    perf_s: float          # analytic latency estimate used by the DP
    size_bytes: float      # per-node DRAM weight storage


@dataclass
class Mapping:
    graph: DnnGraph
    hw: HwConfig
    segments: list[Segment]
    sm: dict[int, SM]                      # segment index -> SM
    choices: dict[str, LayerChoice]        # heavy layer name -> choice
    est_latency_s: float = 0.0             # DP objective value


@dataclass
class LayerReport:
    name: str
    latency_s: float
    comm_s: float
    energy_pj: float
    e_noc_pj: float
    breakdown: dict[str, float] = field(default_factory=dict)


@dataclass
class EvalReport:
    latency_s: float
    energy_pj: float
    energy_breakdown: dict[str, float]
    layers: list[LayerReport]

    @property
    def edp(self) -> float:
        return self.latency_s * self.energy_pj


# -- candidate generation ------------------------------------------------------
#
# The same (layer shape, region shape, layouts) keys recur constantly across
# deep nets, SM candidates, and DL iterations, so candidate tables are
# memoized — but *bounded*: a long multi-config campaign cycles through many
# HwConfigs and an unbounded cache would grow with every one of them.
# ``clear_mapper_caches`` drops everything between hardware configs.

_CACHE_CANDIDATES = 2048      # candidate tables (one per layer/region/DL key)
_CACHE_NODE_LAT = 65536       # per-(part-layer, DL) node latencies (floats)
_CACHE_SCHEDULES = 4096       # Data-Scheduler solves (see _sharing_latency)


@lru_cache(maxsize=_CACHE_CANDIDATES)
def _layer_candidates(hw: HwConfig, layer: Layer, h_shape: int, w_shape: int,
                      dl_in: DataLayout, dl_out: DataLayout,
                      n_wr: int, lm_cap: int
                      ) -> tuple[tuple[int, float, float, LM], ...]:
    """Per-WR best LM for a layer on an ``h x w`` region (scalar backend).

    Returns ``(wr, perf_s, size_bytes, lm)`` tuples sorted by size desc.
    """
    lms = enumerate_lms(layer, h_shape, w_shape, cap=lm_cap)
    best: dict[int, tuple[float, float, LM]] = {}
    for lm in lms:
        pl = part_layer(layer, lm)
        node = part_layer_cost(hw, pl, dl_in, dl_out)
        for wr in wr_candidates(layer, lm, n_wr):
            ce = comm_estimate(layer, lm, wr, hw)
            perf = node.latency_s + ce.latency_s
            size = ce.weight_bytes_per_node
            cur = best.get(wr)
            if cur is None or perf < cur[0]:
                best[wr] = (perf, size, lm)
    out = [(wr, p, s, lm) for wr, (p, s, lm) in best.items()]
    out.sort(key=lambda t: -t[2])
    return tuple(out)


class _BoundedCache:
    """Tiny bounded memo dict with FIFO eviction.

    Reads are plain (GIL-atomic) dict lookups so the hot path takes no lock;
    writes lock only for the insert-and-trim.  FIFO (not strict LRU) is fine
    here: entries are hw-config-scoped and campaigns clear between configs —
    the bound only guards against pathological single-config growth.
    """

    def __init__(self, maxsize: int):
        self.maxsize = maxsize
        self._d: OrderedDict = OrderedDict()
        self._lock = threading.Lock()

    def get(self, key, default=None):
        return self._d.get(key, default)

    def __contains__(self, key) -> bool:
        return key in self._d

    def put(self, key, value) -> None:
        with self._lock:
            self._d[key] = value
            while len(self._d) > self.maxsize:
                self._d.popitem(last=False)

    def put_many(self, items) -> None:
        """Insert ``(key, value)`` pairs under ONE lock acquisition.

        The multi-config fill writes tens of thousands of node latencies per
        batch; per-entry locking would dominate the fill itself.
        """
        with self._lock:
            d = self._d
            for key, value in items:
                d[key] = value
            while len(d) > self.maxsize:
                d.popitem(last=False)

    def clear(self) -> None:
        with self._lock:
            self._d.clear()


_BATCH_CANDS = _BoundedCache(_CACHE_CANDIDATES)
_NODE_LAT = _BoundedCache(_CACHE_NODE_LAT)
_CAND_STRUCT = _BoundedCache(_CACHE_CANDIDATES)
_CAND_BASE = _BoundedCache(_CACHE_CANDIDATES)
_COMM_GEOM = _BoundedCache(_CACHE_CANDIDATES)


def clear_mapper_caches() -> None:
    """Drop every mapper-level memo (candidates, node costs, schedules).

    Campaigns call this between configs to keep long multi-config runs at a
    flat memory footprint.  Most entries are keyed by :class:`HwConfig` and
    carry nothing across configurations anyway; the hw-independent shape
    memos (``_CAND_BASE``, ``_COMM_GEOM``) ARE reusable across configs but
    are dropped too, keeping the memory guarantee simple — ``map_many``
    amortizes them across a whole batch before the next clear.
    """
    _layer_candidates.cache_clear()
    _BATCH_CANDS.clear()
    _NODE_LAT.clear()
    _CAND_STRUCT.clear()
    _CAND_BASE.clear()
    _COMM_GEOM.clear()
    _sharing_latency.cache_clear()
    part_layer_cost.cache_clear()
    tile_access_cost.cache_clear()
    sequential_access_cost.cache_clear()


def mapper_cache_stats() -> dict[str, int]:
    """Current size of every mapper-level memo (observability snapshot).

    Keys mirror the module-level cache names; campaigns fold these into
    their metrics snapshot so memo growth is visible without a debugger.
    """
    return {
        "layer_candidates": _layer_candidates.cache_info().currsize,
        "batch_candidates": len(_BATCH_CANDS._d),
        "node_latencies": len(_NODE_LAT._d),
        "candidate_structs": len(_CAND_STRUCT._d),
        "candidate_bases": len(_CAND_BASE._d),
        "comm_geometries": len(_COMM_GEOM._d),
        "schedules": len(_SCHED_MEMO._d),
        "part_layer_costs": part_layer_cost.cache_info().currsize,
        "tile_access_costs": tile_access_cost.cache_info().currsize,
        "sequential_access_costs":
            sequential_access_cost.cache_info().currsize,
    }


def _batched_node_latencies(hw: HwConfig,
                            specs: list[tuple[Layer, DataLayout, DataLayout]]
                            ) -> np.ndarray:
    """Node latency for every ``(part-layer, dl_in, dl_out)`` spec, memoized.

    Misses are costed in ONE chunked :func:`engine.batch_cost.batch_part_cost`
    call — this is the mapper's whole-segment candidate costing hot path.
    """
    keys = [(hw,) + s for s in specs]
    # single cache read per key: a concurrent clear_mapper_caches() (another
    # campaign thread finishing its config) must never be able to swap a
    # value source mid-call — fresh results are kept locally
    vals = [_NODE_LAT.get(key) for key in keys]
    missing: dict[tuple, int] = {}
    for key, v in zip(keys, vals):
        if v is None and key not in missing:
            missing[key] = len(missing)
    if missing:
        from ..engine.batch_cost import batch_part_cost
        lat = batch_part_cost([hw], [k[1:] for k in missing],
                              spec_chunk=1024).latency_s[0]
        fresh = {key: float(lat[j]) for key, j in missing.items()}
        _NODE_LAT.put_many(fresh.items())
        vals = [fresh[key] if v is None else v
                for key, v in zip(keys, vals)]
    return np.array(vals)


def _fill_node_latencies_multi(requests) -> dict:
    """Warm ``_NODE_LAT`` for several configs' spec lists in one engine call.

    ``requests`` is ``[(hw, [spec, ...]), ...]`` with ``spec = (part-layer,
    dl_in, dl_out)``.  Missing cells are costed through ONE multi-config
    ``batch_part_cost_paired`` call per shared :class:`PimConstraints` group
    — each (config, spec) pair rides the engine's spec axis with its config
    fields broadcast alongside, so compute scales with the number of missing
    pairs (configs' candidate sets are mostly disjoint; a full ``[N configs]
    x [union specs]`` grid would waste ~N x the work) while the dispatch
    count drops from one per config to one per batch.

    Returns the freshly costed ``{(hw,) + spec: latency}`` dict.  Callers
    consume it directly (falling back to :func:`_batched_node_latencies` for
    anything not in it): the fills are larger than any single cache bound
    should have to accommodate, so round-tripping a huge batch through the
    FIFO-bounded ``_NODE_LAT`` could evict its own warm entries before they
    are read.  The memo write-back is advisory warming for later sweeps, and
    a concurrent ``clear_mapper_caches`` between fill and read only costs a
    single-config re-derivation.
    """
    return _dispatch_node_fill(requests).resolve()


class _PendingFill:
    """An in-flight multi-config node-latency fill.

    Holds one :class:`~repro.engine.overlap.PendingPairedCost` per
    constraints group; :meth:`resolve` blocks on the device rows (once),
    builds the ``{(hw,) + spec: latency}`` dict, and warms ``_NODE_LAT``
    — the exact tail of the serial ``_fill_node_latencies_multi``.
    """

    __slots__ = ("_groups", "_fresh")

    def __init__(self, groups):
        self._groups = groups
        self._fresh: dict | None = None

    @property
    def ready(self) -> bool:
        return (self._fresh is not None
                or all(p.ready for _, p in self._groups))

    def resolve(self) -> dict:
        if self._fresh is None:
            fresh: dict[tuple, float] = {}
            for pairs, pending in self._groups:
                lat = pending.latency_row()
                for (hw, s), v in zip(pairs, lat):
                    fresh[(hw,) + s] = float(v)
            if fresh:
                _NODE_LAT.put_many(fresh.items())
            self._fresh = fresh
            self._groups = None
        return self._fresh


def _dispatch_node_fill(requests) -> _PendingFill:
    """Dispatch phase of :func:`_fill_node_latencies_multi`.

    Enqueues the paired sweeps for every missing cell and returns a
    :class:`_PendingFill` without blocking on the device results, so
    callers can run host work while the costs are in flight.
    """
    missing: dict[HwConfig, dict[tuple, None]] = {}
    for hw, specs in requests:
        d = missing.setdefault(hw, {})
        for s in specs:
            if (hw,) + s not in _NODE_LAT:
                d[s] = None
    missing = {hw: d for hw, d in missing.items() if d}
    if not missing:
        return _PendingFill(())
    from ..engine.overlap import dispatch_paired_latency
    groups: dict[object, list[HwConfig]] = {}
    for hw in missing:  # one engine batch must share one PimConstraints
        groups.setdefault(hw.cons, []).append(hw)
    out = []
    for hws in groups.values():
        pairs = [(hw, s) for hw in hws for s in missing[hw]]
        pending = dispatch_paired_latency([hw for hw, _ in pairs],
                                          [s for _, s in pairs])
        out.append((pairs, pending))
    return _PendingFill(out)


def _prefetch_candidates_multi(key_lists) -> dict[tuple, tuple]:
    """Cost every missing candidate table of several key sets in one call.

    ``key_lists`` holds one ``_cand_key`` list per hardware config (the hw is
    the first key element); the node latencies of every missing table are
    costed through one multi-config :func:`_fill_node_latencies_multi` pass.
    Returns a table per requested key, like
    :meth:`PimMapper._prefetch_candidates` (which delegates here) — callers
    consume the returned dict rather than re-reading ``_BATCH_CANDS``, so a
    concurrent ``clear_mapper_caches()`` can only ever cost re-derivation,
    never correctness.
    """
    return _dispatch_candidates_multi(key_lists).resolve()


class _PendingTables:
    """In-flight candidate tables: node fills dispatched, tables not built.

    :meth:`resolve` blocks on the underlying :class:`_PendingFill` and
    runs the table-construction tail of ``_prefetch_candidates_multi``.
    """

    __slots__ = ("_out", "_work", "_fill")

    def __init__(self, out, work, fill):
        self._out = out
        self._work = work
        self._fill = fill

    @property
    def ready(self) -> bool:
        return not self._work or self._fill.ready

    def resolve(self) -> dict[tuple, tuple]:
        if self._work:
            with trace.span("cand_build", cat="mapper",
                            tables=len(self._work)):
                fresh = self._fill.resolve()
                for hw, key, struct, specs in self._work:
                    node_lat = _node_lat_from(fresh, hw, specs)
                    table = _layer_candidates_batched(struct, node_lat)
                    self._out[key] = table
                    _BATCH_CANDS.put(key, table)
            self._work = ()
        return self._out


def _dispatch_candidates_multi(key_lists) -> _PendingTables:
    """Dispatch phase of :func:`_prefetch_candidates_multi`.

    The key lists may be lazy: the ``cand_dispatch`` span covers the key
    enumeration that iterating them runs, and counts the distinct ``keys``
    and the tables ``built`` (not in ``_BATCH_CANDS``).
    """
    with trace.span("cand_dispatch", cat="mapper") as sp:
        out: dict[tuple, tuple] = {}
        work = []
        for keys in key_lists:
            for key in keys:
                if key in out:
                    continue
                got = _BATCH_CANDS.get(key)
                if got is None:
                    out[key] = ()  # placeholder: dedupes repeated misses
                    hw, layer, h, w, din, dout, n_wr, lm_cap = key
                    struct = _cand_struct(hw, layer, h, w, n_wr, lm_cap)
                    work.append((hw, key, struct,
                                 [(pl, din, dout) for pl in struct.uniq_pls]))
                else:
                    out[key] = got
        sp["keys"], sp["built"] = len(out), len(work)
        if not work:
            return _PendingTables(out, (), None)
        fill = _dispatch_node_fill([(hw, specs) for hw, _, _, specs in work])
    return _PendingTables(out, work, fill)


def _node_lat_from(fresh: dict, hw: HwConfig, specs) -> np.ndarray:
    """Node latencies from a fill's returned dict, memo-backed.

    Prefers the freshly costed values (immune to FIFO self-eviction on huge
    fills), falls back per key to the memo, and re-derives through
    :func:`_batched_node_latencies` only if a concurrent clear dropped both.
    """
    vals = [fresh.get((hw,) + s) for s in specs]
    if any(v is None for v in vals):
        vals = [_NODE_LAT.get((hw,) + s) if v is None else v
                for v, s in zip(vals, specs)]
    if any(v is None for v in vals):
        return _batched_node_latencies(hw, specs)
    return np.array(vals)


@dataclass
class _CandStruct:
    """The DL-independent half of a candidate sweep for (layer, region).

    Built once per (hw, layer, region-shape) and reused across every DL
    iteration and segment that revisits the same shapes — only the node
    latencies (which depend on the data layouts) are re-gathered per key.
    Part-layers are deduped (different P_orders and collapsed ceil-divisions
    share one node cost); ``pair_pl`` maps each (LM x WR) pair to its row in
    ``uniq_pls``.
    """

    uniq_pls: list[Layer]               # deduped part_layer rows
    pair_pl: np.ndarray                 # (LM x WR) pair -> uniq_pls index
    pair_lm_of: list[LM]                # (LM x WR) pair -> LM
    comm_lat: np.ndarray                # vectorized comm_estimate per pair
    stored: np.ndarray                  # weight bytes/node per pair
    by_wr: list[tuple[int, np.ndarray]]  # WR -> pair indices, first-seen order


@dataclass
class _CandBase:
    """The hardware-independent half of :class:`_CandStruct`.

    LM enumeration, part-layer dedup, and the (LM x WR) pair structure
    depend only on (layer, region shape, mapper knobs) — never on the
    :class:`HwConfig` — so one base serves every config that visits the
    shape.  Cached separately from the per-hw comm arrays: a multi-config
    batch builds each base once instead of once per config.
    """

    uniq_pls: list[Layer]
    pair_pl: np.ndarray
    pair_lm_of: list[LM]
    pair_wrs: list[int]
    by_wr: list[tuple[int, np.ndarray]]


def _cand_base(layer: Layer, h_shape: int, w_shape: int,
               n_wr: int, lm_cap: int) -> _CandBase:
    key = (layer, h_shape, w_shape, n_wr, lm_cap)
    got = _CAND_BASE.get(key)
    if got is not None:
        return got
    lms = enumerate_lms(layer, h_shape, w_shape, cap=lm_cap)
    uniq_pls: list[Layer] = []
    pl_index: dict[Layer, int] = {}
    pair_lms: list[LM] = []
    pair_wrs: list[int] = []
    pair_pl: list[int] = []
    for lm in lms:
        pl = part_layer(layer, lm)
        pi = pl_index.get(pl)
        if pi is None:
            pi = pl_index[pl] = len(uniq_pls)
            uniq_pls.append(pl)
        for wr in wr_candidates(layer, lm, n_wr):
            pair_lms.append(lm)
            pair_wrs.append(wr)
            pair_pl.append(pi)
    by_wr: dict[int, list[int]] = {}
    for p, wr in enumerate(pair_wrs):       # first-seen WR order, like the
        by_wr.setdefault(wr, []).append(p)  # scalar best-dict insertion
    base = _CandBase(
        uniq_pls=uniq_pls, pair_pl=np.array(pair_pl, dtype=np.intp),
        pair_lm_of=pair_lms, pair_wrs=pair_wrs,
        by_wr=[(wr, np.array(idxs, dtype=np.intp))
               for wr, idxs in by_wr.items()])
    _CAND_BASE.put(key, base)
    return base


def _cand_struct(hw: HwConfig, layer: Layer, h_shape: int, w_shape: int,
                 n_wr: int, lm_cap: int) -> _CandStruct:
    key = (hw, layer, h_shape, w_shape, n_wr, lm_cap)
    got = _CAND_STRUCT.get(key)
    if got is not None:
        return got
    base = _cand_base(layer, h_shape, w_shape, n_wr, lm_cap)
    m = len(base.pair_lm_of)
    dbytes = hw.cons.data_bits // 8
    psbytes = hw.cons.psum_bits // 8
    if m == 0 or not layer.is_heavy:
        z = np.zeros(m)
        comm_lat, stored = z, z.copy()
    else:
        # the ring/sharing geometry is hw-independent: compute it once per
        # (shape, data-width) key and re-apply only the per-hw scalars —
        # multi-config batches revisit the same shapes under many configs
        gkey = (layer, h_shape, w_shape, n_wr, lm_cap, dbytes, psbytes)
        geom = _COMM_GEOM.get(gkey)
        if geom is None:
            geom = comm_batch_geometry(layer, base.pair_lm_of, base.pair_wrs,
                                       dbytes, psbytes)
            _COMM_GEOM.put(gkey, geom)
        comm_lat, _, stored = comm_eval_geometry(geom, hw)
    struct = _CandStruct(
        uniq_pls=base.uniq_pls, pair_pl=base.pair_pl,
        pair_lm_of=base.pair_lm_of, comm_lat=comm_lat, stored=stored,
        by_wr=base.by_wr)
    _CAND_STRUCT.put(key, struct)
    return struct


def _layer_candidates_batched(struct: _CandStruct, node_lat: np.ndarray
                              ) -> tuple[tuple[int, float, float, LM], ...]:
    """Assemble one candidate table from pre-batched node latencies.

    ``node_lat[i]`` is the node cost of ``struct.uniq_pls[i]``; the (LM x WR)
    communication axis comes pre-scored from the vectorized
    :func:`partition.comm_estimate_batch` and is reduced per WR with the
    same first-strict-< winner rule as the scalar loop (first-argmin).
    """
    perf = node_lat[struct.pair_pl] + struct.comm_lat
    out = []
    for wr, idxs in struct.by_wr:
        p = idxs[int(np.argmin(perf[idxs]))]
        out.append((wr, float(perf[p]), float(struct.stored[p]),
                    struct.pair_lm_of[p]))
    out.sort(key=lambda t: -t[2])
    return tuple(out)


# -- Algorithm 2: DP over capacity --------------------------------------------


import numpy as np


def _resolve_reduce(reduce: str) -> str:
    # "auto" is NumPy on every backend: the DP runs in f64 (identical
    # choices to the scalar reference), and Mosaic compiles no f64 kernel
    if reduce == "auto":
        return "numpy"
    if reduce not in ("numpy", "pallas"):
        raise ValueError(f"unknown DP reduce {reduce!r}; "
                         f"expected 'auto', 'numpy' or 'pallas'")
    return reduce


def minplus_convolve(tab: np.ndarray, best: np.ndarray, *,
                     reduce: str = "auto") -> tuple[np.ndarray, np.ndarray]:
    """Min-plus convolution ``out[c] = min_i(tab[i] + best[c - i])`` + argmin.

    Array form of the segment-combination step of Algorithm 2: every
    ``(cap, prefix-budget)`` split of the shared per-node DRAM budget is
    scored at once and reduced with a min + *first*-argmin over the prefix
    budget ``i`` — the exact first-strict-< winner of the old sequential
    i-ascending update loop.  ``reduce`` picks vectorized NumPy (``"auto"``)
    or the Pallas ``kernels.dse_eval.minplus_rows`` kernel, which runs in
    f64 and so only in interpret mode (the kernel's parity tests).

    Returns ``(out, arg)`` with ``arg[c] = -1`` where no feasible split
    exists (``out[c]`` stays ``inf``), matching the old loop's untouched
    ``arg_i`` cells.
    """
    u = len(tab) - 1
    ext = np.concatenate([np.full(u, INF), best])
    # rows[c, i] = best[c - i] for i <= c, inf otherwise (Toeplitz of best)
    rows = np.lib.stride_tricks.sliding_window_view(ext, u + 1)[:, ::-1]
    if _resolve_reduce(reduce) == "pallas":
        from ..kernels import dse_eval
        from ..runtime import x64
        with x64():
            mn, idx = dse_eval.minplus_rows(tab, np.ascontiguousarray(rows))
        mn = np.asarray(mn)
        idx = np.asarray(idx)
    else:
        scores = tab[None, :] + rows
        idx = scores.argmin(axis=1)
        mn = scores[np.arange(scores.shape[0]), idx]  # one reduction pass
    arg = np.where(np.isfinite(mn), idx, -1).astype(np.int32)
    return mn, arg


class RegionTable:
    """Knapsack result for one region: monotone perf-vs-capacity + backtrack.

    The per-layer DP step is array-form over the full candidate axis: every
    ``(candidate, cap)`` cell is scored at once (``perf[cap - size] + perf_c``
    where feasible) and the min + first-argmin over candidates — the exact
    first-strict-< winner of the old per-candidate Python loop — runs either
    in NumPy (``reduce="auto"``) or in the Pallas
    ``kernels.dse_eval.argmin_rows`` reduction (``reduce="pallas"``, f64, so
    interpret mode only — the kernel's parity tests).

    Backtracking is array-based (O(layers x units) int16), replayed in
    reverse: at budget ``cap`` layer ``l`` chose candidate ``choice[l, eff]``
    where ``eff = eff_cap[l, cap]`` is the cell the monotone fill borrowed
    from; the remaining budget is ``eff - size(choice)``.
    """

    def __init__(self, layer_cands, units: int, unit_bytes: float,
                 *, reduce: str = "auto"):
        reduce = _resolve_reduce(reduce)
        self.layer_cands = layer_cands
        self.units = units
        perf = np.zeros(units + 1)
        self.choice = np.full((len(layer_cands), units + 1), -1, np.int16)
        self.eff = np.zeros((len(layer_cands), units + 1), np.int32)
        self.sizes = []
        caps = np.arange(units + 1)
        for li, (lname, cands) in enumerate(layer_cands):
            sizes = np.minimum(units + 1,
                               np.ceil(np.array([c[2] for c in cands])
                                       / unit_bytes)).astype(np.int64)
            self.sizes.append(sizes)
            perfs = np.array([c[1] for c in cands])
            if len(cands) == 0:  # layer with no legal LM: stays infeasible
                nperf = np.full(units + 1, INF)
            else:
                # [C, units+1]: candidate ci at cap spends sizes[ci], leaving
                # the prefix budget cap - sizes[ci]; infeasible cells get INF
                left = caps[None, :] - sizes[:, None]
                feas = left >= 0
                scores = np.where(
                    feas, perf[np.clip(left, 0, units)] + perfs[:, None], INF)
                if reduce == "pallas":
                    from ..kernels import dse_eval
                    from ..runtime import x64
                    with x64():
                        mn, idx = dse_eval.argmin_rows(scores.T)
                    nperf = np.asarray(mn)
                    ci = np.asarray(idx)
                else:
                    nperf = scores.min(axis=0)
                    ci = scores.argmin(axis=0)
                self.choice[li] = np.where(np.isfinite(nperf), ci, -1)
            # monotone fill, tracking effective cap
            eff = np.arange(units + 1, dtype=np.int32)
            run = np.minimum.accumulate(nperf)
            borrowed = nperf > run
            # effective cap = last index where run decreased
            last = np.where(~borrowed, eff, 0)
            eff = np.maximum.accumulate(last)
            self.eff[li] = eff
            perf = run
        self.perf = perf

    def backtrack(self, cap: int) -> dict[str, int]:
        picks: dict[str, int] = {}
        cap = int(min(cap, self.units))
        for li in range(len(self.layer_cands) - 1, -1, -1):
            lname, cands = self.layer_cands[li]
            eff = int(self.eff[li, cap])
            ci = int(self.choice[li, eff])
            if ci < 0:  # infeasible cell: fall back to fastest candidate
                if not cands:
                    # a layer with zero legal candidates has nothing to fall
                    # back on — leave it unpicked so infeasibility stays
                    # contained to this layer instead of raising here
                    continue
                ci = min(range(len(cands)), key=lambda i: cands[i][1])
                picks[lname] = ci
                continue
            picks[lname] = ci
            cap = eff - int(self.sizes[li][ci])
        return picks


# -- the mapper ---------------------------------------------------------------


class PimMapper:
    """Sec. VI mapper.

    ``backend="batched"`` (default) costs every (LM x WR x layer x region)
    candidate of a network through the vectorized engine
    (``engine.batch_cost.batch_part_cost`` + ``partition.comm_estimate_batch``)
    in one chunked call per mapping pass; ``backend="scalar"`` keeps the
    original one-candidate-at-a-time reference path.  Both produce identical
    mappings (the parity tests pin choices/SM exactly and latencies to 1e-6).
    """

    def __init__(self, hw: HwConfig, *, max_optim_iter: int = 3,
                 cap_units: int = 1024, lm_cap: int = 200, n_wr: int = 5,
                 sm_max_regions: int | None = None,
                 dl_max_group: int = 32, backend: str = "batched",
                 dp_reduce: str = "auto"):
        if backend not in BACKENDS:
            raise ValueError(f"unknown mapper backend {backend!r}; "
                             f"expected one of {BACKENDS}")
        self.hw = hw
        self.max_optim_iter = max_optim_iter
        self.cap_units = cap_units
        self.lm_cap = lm_cap
        self.n_wr = n_wr
        self.sm_max_regions = sm_max_regions
        self.dl_max_group = dl_max_group
        self.backend = backend
        self.dp_reduce = dp_reduce

    # ---- candidate costing (scalar or batched) -------------------------------
    def _cand_key(self, layer: Layer, region_h: int, region_w: int,
                  din: DataLayout, dout: DataLayout) -> tuple:
        return (self.hw, layer, region_h, region_w, din, dout,
                self.n_wr, self.lm_cap)

    def _candidates(self, layer: Layer, region_h: int, region_w: int,
                    din: DataLayout, dout: DataLayout):
        key = self._cand_key(layer, region_h, region_w, din, dout)
        if self.backend == "scalar":
            return _layer_candidates(*key)
        got = _BATCH_CANDS.get(key)
        if got is None:  # cache miss (evicted or cleared): fill just this
            got = self._prefetch_candidates([key])[key]
        return got

    def _prefetch_candidates(self, keys: Iterable[tuple]
                             ) -> dict[tuple, tuple]:
        """Cost every missing candidate table in one batched engine call.

        Returns a table per requested key.  Callers consume the returned
        dict rather than re-reading ``_BATCH_CANDS`` — a concurrent
        ``clear_mapper_caches()`` (another campaign thread finishing its
        config) may empty or evict the shared cache at any point, and must
        only ever cost re-derivation, never correctness.
        """
        return _prefetch_candidates_multi([keys])

    # ---- DL bookkeeping ------------------------------------------------------
    def _default_dl(self, channels: int) -> DataLayout:
        g = 1
        while g * 2 <= min(channels, 16):
            g *= 2
        return DataLayout("BCHW", g)

    def _init_dls(self, g: DnnGraph) -> dict[str, tuple[DataLayout, DataLayout]]:
        dls = {}
        for layer in g.layers:
            dls[layer.name] = (self._default_dl(layer.C),
                               self._default_dl(layer.K))
        return dls

    # ---- Algorithm 1 ----------------------------------------------------------
    def map(self, graph: DnnGraph) -> Mapping:
        with trace.span("map", graph=graph.name, configs=1):
            return self._map(graph)

    def _map(self, graph: DnnGraph) -> Mapping:
        segments = graph.segments()
        dls = self._init_dls(graph)
        mapping: Mapping | None = None
        for it in range(self.max_optim_iter):
            mapping = self._solve_sm_lm_wr(graph, segments, dls)
            with trace.span("dl_optimize", cat="mapper") as sp:
                table = None
                if self.backend == "batched":
                    table = self._dl_sweep_table(graph, mapping)
                    sp["specs"] = len(table)
                dls = self._optimize_dl(graph, mapping, dls, table=table)
            for name, ch in mapping.choices.items():
                ch.dl_in, ch.dl_out = dls[name]
        return mapping

    def _with_hw(self, hw: HwConfig) -> "PimMapper":
        if hw == self.hw:
            return self
        return PimMapper(hw, max_optim_iter=self.max_optim_iter,
                         cap_units=self.cap_units, lm_cap=self.lm_cap,
                         n_wr=self.n_wr, sm_max_regions=self.sm_max_regions,
                         dl_max_group=self.dl_max_group, backend=self.backend,
                         dp_reduce=self.dp_reduce)

    @trace.traced("map_many", argspec=lambda self, graph, cfgs, **kw:
                  {"graph": graph.name, "configs": len(cfgs)})
    def map_many(self, graph: DnnGraph, cfgs: Sequence[HwConfig],
                 *, on_infeasible: str = "raise") -> list[Mapping | None]:
        """Map ``graph`` under several hardware configs, batched across them.

        Every config's Algorithm-1 iteration runs in lockstep so each phase's
        candidate sweep — the (SM x LM x WR x layer x region) costing and the
        DL layout sweep — is costed in ONE multi-config
        ``engine.batch_part_cost`` call (the engine's ``[N configs]`` axis)
        instead of one engine round-trip per config.  Batching only pre-warms
        the shared memos; the per-config DP/backtracking path is the exact
        :meth:`map` code, so results are identical to per-config ``map()``
        calls (pinned by the parity tests).

        ``on_infeasible`` controls configs with no capacity-feasible mapping:
        ``"raise"`` propagates the :class:`RuntimeError` like :meth:`map`
        (the default); ``"none"`` leaves ``None`` in that config's slot and
        continues the rest of the batch.
        """
        gen = self.map_many_phases(graph, cfgs, on_infeasible=on_infeasible)
        while True:
            try:
                next(gen)
            except StopIteration as stop:
                return stop.value

    def map_many_phases(self, graph: DnnGraph, cfgs: Sequence[HwConfig],
                        *, on_infeasible: str = "raise"):
        """Phase generator behind :meth:`map_many`.

        Yields once per in-flight engine dispatch (the candidate-table
        sweep and the DL sweep of each Algorithm-1 iteration) and returns
        the mapping list via ``StopIteration.value``.  At each yield the
        just-dispatched device work has NOT been synced — an
        :class:`~repro.engine.overlap.OverlapExecutor` driving this
        generator runs deferred host work (the previous wave's scheduling
        and accounting) in that window.  Driving the generator straight to
        exhaustion is exactly :meth:`map_many`; both paths execute this
        one code body, so overlapped and serial results are identical by
        construction.
        """
        if on_infeasible not in ("raise", "none"):
            raise ValueError(f"unknown on_infeasible {on_infeasible!r}; "
                             f"expected 'raise' or 'none'")
        subs = [self._with_hw(cfg) for cfg in cfgs]
        return self._map_many_gen(graph, subs, on_infeasible)

    def _map_many_gen(self, graph: DnnGraph, subs: list["PimMapper"],
                      on_infeasible: str):
        if self.backend == "scalar":  # reference path: plain per-config loop
            out: list[Mapping | None] = []
            for sub in subs:
                try:
                    out.append(sub.map(graph))
                except RuntimeError:
                    if on_infeasible == "raise":
                        raise
                    out.append(None)
            return out
        segments = graph.segments()
        dls = [sub._init_dls(graph) for sub in subs]
        mappings: list[Mapping | None] = [None] * len(subs)
        alive = list(range(len(subs)))
        seg_sms = {i: subs[i]._seg_sms(graph, segments)
                   for i in range(len(subs))}
        # every span below closes before the next yield: the executor runs
        # deferred work there, and it must not nest inside these phases
        for _ in range(self.max_optim_iter):
            pending_tables = _dispatch_candidates_multi(
                subs[i]._solve_keys(graph, segments, seg_sms[i], dls[i])
                for i in alive)
            yield pending_tables  # candidate costs in flight
            # the resolved tables are handed straight to each sub's solve —
            # a batch whose key union exceeds the _BATCH_CANDS bound must
            # not self-evict into per-config engine fills
            tables = pending_tables.resolve()
            for i in list(alive):
                try:
                    mappings[i] = subs[i]._solve_sm_lm_wr(
                        graph, segments, dls[i], seg_sms=seg_sms[i],
                        cand_tables=tables)
                except RuntimeError:
                    if on_infeasible == "raise":
                        raise
                    mappings[i] = None
                    alive.remove(i)
            with trace.span("dl_dispatch", cat="mapper") as sp:
                sweeps = {i: subs[i]._dl_sweep_specs(graph, mappings[i])
                          for i in alive}
                pending_fill = _dispatch_node_fill(
                    [(subs[i].hw, sweeps[i][1]) for i in alive])
                if trace.current() is not None:
                    sp["specs"] = sum(len(sweeps[i][1]) for i in alive)
            yield pending_fill  # DL-sweep costs in flight
            # the same counts as its dispatch (none when tracing is off)
            with trace.span("dl_optimize", cat="mapper", **sp):
                fresh = pending_fill.resolve()
                for i in alive:
                    entries, specs = sweeps[i]
                    lat = _node_lat_from(fresh, subs[i].hw, specs)
                    table = {e: float(l) for e, l in zip(entries, lat)}
                    dls[i] = subs[i]._optimize_dl(graph, mappings[i], dls[i],
                                                  table=table)
                    for name, ch in mappings[i].choices.items():
                        ch.dl_in, ch.dl_out = dls[i][name]
        return mappings

    def _seg_sms(self, graph: DnnGraph, segments: list[Segment]):
        return [gen_sm_candidates(graph, seg, self.hw.na_row, self.hw.na_col,
                                  self.sm_max_regions) for seg in segments]

    def _solve_keys(self, graph: DnnGraph, segments: list[Segment],
                    seg_sms, dls) -> Iterator[tuple]:
        """Every candidate-table key one ``_solve_sm_lm_wr`` pass touches,
        lazily: the dispatch that consumes them times their enumeration."""
        for seg, sms in zip(segments, seg_sms):
            for sm in sms:
                for ri, region in enumerate(sm.regions):
                    for bi in sm.branches_of(ri):
                        for lname in seg.branches[bi].heavy_layers(graph):
                            din, dout = dls[lname]
                            yield self._cand_key(
                                graph.layer(lname), region.h_shape,
                                region.w_shape, din, dout)

    @trace.traced("dp_solve", cat="mapper",
                  argspec=lambda self, graph, segments, *a, **kw:
                  {"segments": len(segments)})
    def _solve_sm_lm_wr(self, graph: DnnGraph, segments: list[Segment],
                        dls, seg_sms=None, cand_tables=None) -> Mapping:
        hw = self.hw
        units = self.cap_units
        unit_bytes = hw.node_dram_capacity / units
        if seg_sms is None:
            seg_sms = self._seg_sms(graph, segments)
        if cand_tables is None:
            cand_tables = {}
            if self.backend == "batched":
                # every (LM x WR x layer x region-shape) candidate of the
                # whole network is costed up front in one chunked engine
                # call; the costing loop below reads the returned dict, so
                # cache eviction or a concurrent clear can never force
                # per-key dispatches (map_many passes its own multi-config
                # prefetch result in for the same reason)
                cand_tables = self._prefetch_candidates(
                    self._solve_keys(graph, segments, seg_sms, dls))
        # Per segment: list of (sm, seg_perf, reg_tabs) where seg_perf[cap] is
        # max over its regions' knapsack tables at per-node budget cap.
        seg_tables = []
        for seg, sms in zip(segments, seg_sms):
            per_sm = []
            for sm in sms:
                reg_tabs = []
                seg_perf = np.zeros(units + 1)
                for ri, region in enumerate(sm.regions):
                    layer_cands = []
                    for bi in sm.branches_of(ri):
                        for lname in seg.branches[bi].heavy_layers(graph):
                            layer = graph.layer(lname)
                            din, dout = dls[lname]
                            key = self._cand_key(layer, region.h_shape,
                                                 region.w_shape, din, dout)
                            cands = cand_tables.get(key)
                            if cands is None:
                                cands = self._candidates(
                                    layer, region.h_shape, region.w_shape,
                                    din, dout)
                            layer_cands.append((lname, cands))
                    if not layer_cands:
                        continue
                    tab = RegionTable(layer_cands, units, unit_bytes,
                                      reduce=self.dp_reduce)
                    seg_perf = np.maximum(seg_perf, tab.perf)
                    reg_tabs.append((region, tab))
                if np.isinf(seg_perf[units]) and reg_tabs:
                    continue  # SM infeasible even at full capacity
                per_sm.append((sm, seg_perf, reg_tabs))
            has_heavy = any(b.heavy_layers(graph) for b in seg.branches)
            if has_heavy and not per_sm:
                raise RuntimeError(
                    f"no feasible mapping under DRAM capacity for segment "
                    f"{seg.index} of {graph.name}")
            seg_tables.append(per_sm)

        # combine SMs: best per (segment, cap); then min-plus convolve
        tab = np.zeros(units + 1)
        seg_choice: list[tuple[np.ndarray, np.ndarray, np.ndarray] | None] = []
        for per_sm in seg_tables:
            if not per_sm:
                seg_choice.append(None)
                continue
            best = np.full(units + 1, INF)
            best_sm = np.full(units + 1, -1, np.int32)
            for smi, (_, seg_perf, _) in enumerate(per_sm):
                better = seg_perf < best
                best = np.where(better, seg_perf, best)
                best_sm[better] = smi
            # arg_i[c] = prefix budget used; min-plus convolution, kernelized
            ntab, arg_i = minplus_convolve(tab, best, reduce=self.dp_reduce)
            seg_choice.append((best_sm, arg_i, None))
            # monotone fill (keep arg of the borrowed cell): a cell is
            # borrowed iff a strictly smaller value exists at a lower cap,
            # and takes the arg of the last non-borrowed cell below it
            tab = np.minimum.accumulate(ntab)
            src = np.maximum.accumulate(
                np.where(ntab <= tab, np.arange(units + 1), 0))
            arg_i[:] = arg_i[src]

        if not np.isfinite(tab[units]):
            raise RuntimeError("no feasible mapping under DRAM capacity")

        # backtrack: recover per-segment (sm index, cap_seg)
        plan: list[tuple[int, int, int]] = []  # (seg_idx, smi, cap_seg)
        cap = units
        for si in range(len(seg_tables) - 1, -1, -1):
            ch = seg_choice[si]
            if ch is None:
                continue
            best_sm, arg_i, _ = ch
            i = int(arg_i[cap])
            if i < 0:
                i = 0
            cap_seg = cap - i
            # the seg table is monotone: find the smallest budget achieving it
            smi = int(best_sm[min(cap_seg, units)])
            plan.append((si, smi, cap_seg))
            cap = i

        choices: dict[str, LayerChoice] = {}
        sm_chosen: dict[int, SM] = {}
        for si, smi, cap_seg in reversed(plan):
            per_sm = seg_tables[si]
            if smi < 0 or not per_sm:
                smi = 0
            sm, seg_perf, reg_tabs = per_sm[smi]
            sm_chosen[si] = sm
            for region, rtab in reg_tabs:
                pick = rtab.backtrack(cap_seg)
                for lname, cands in rtab.layer_cands:
                    if not cands:  # zero-candidate layer: nothing to choose
                        continue
                    ci = pick.get(lname, 0)
                    wr, p, size, lm = cands[ci]
                    din, dout = dls[lname]
                    choices[lname] = LayerChoice(lm, wr, din, dout, region,
                                                 p, size)
        return Mapping(graph, hw, segments, sm_chosen, choices,
                       est_latency_s=float(tab[units]))

    # ---- DL alternated pass (Sec. VI-C) ---------------------------------------
    def _din_universe(self) -> list[DataLayout]:
        """Every DLi a layer can inherit: any predecessor's swept DLo or a
        default layout — BHWC plus power-of-two channel groups (the cost
        model clamps groups beyond the fmap's channel count)."""
        outs = [DataLayout("BHWC")]
        g = 1
        while g <= max(self.dl_max_group, 16):
            outs.append(DataLayout("BCHW", g))
            g *= 2
        return outs

    def _dl_sweep_specs(self, graph: DnnGraph, mapping: Mapping
                        ) -> tuple[list[tuple], list[tuple]]:
        """(entries, part-layer specs) of the full per-layer layout sweep."""
        entries: list[tuple] = []
        specs: list[tuple] = []
        for name, ch in mapping.choices.items():
            layer = graph.layer(name)
            pl = part_layer(layer, ch.lm)
            for din in self._din_universe():
                for dout in enumerate_layouts(layer.K, self.dl_max_group):
                    entries.append((name, din, dout))
                    specs.append((pl, din, dout))
        return entries, specs

    def _dl_sweep_table(self, graph: DnnGraph, mapping: Mapping
                        ) -> dict[tuple, float]:
        """Latency of every (layer, DLi, DLo) sweep point, batched.

        One chunked engine call covers the full layout sweep of every heavy
        chosen layer — the sequential DLo(pred)=DLi(succ) propagation then
        just reads the table instead of costing per candidate.
        """
        entries, specs = self._dl_sweep_specs(graph, mapping)
        lat = _batched_node_latencies(self.hw, specs)
        return {e: float(l) for e, l in zip(entries, lat)}

    def _optimize_dl(self, graph: DnnGraph, mapping: Mapping, dls,
                     table: dict | None = None):
        """Algorithm 1's DL pass; ``table`` is the batched layout sweep,
        without one each candidate is costed on the scalar path."""
        hw = self.hw
        new: dict[str, tuple[DataLayout, DataLayout]] = {}
        out_dl: dict[str, DataLayout] = {}
        for name in graph.topo_order():
            layer = graph.layer(name)
            preds = graph.preds(name)
            if preds:
                din = out_dl[preds[0]]
                for p in preds[1:]:  # dependency constraint: DLo(pred)=DLi(succ)
                    out_dl[p] = din
            else:
                din = self._default_dl(layer.C)
            if layer.is_heavy and name in mapping.choices:
                ch = mapping.choices[name]
                pl = part_layer(layer, ch.lm)
                best, best_lat = None, INF
                for cand in enumerate_layouts(layer.K, self.dl_max_group):
                    if table is not None:
                        lat = table.get((name, din, cand))
                        if lat is None:  # DLi outside the swept universe
                            lat = part_layer_cost(hw, pl, din, cand).latency_s
                    else:
                        lat = part_layer_cost(hw, pl, din, cand).latency_s
                    if lat < best_lat:
                        best, best_lat = cand, lat
                out_dl[name] = best
            else:
                out_dl[name] = din  # aux layers pass data through
            new[name] = (din, out_dl[name])
        # refresh DLi from (possibly rewritten) predecessor DLo
        final: dict[str, tuple[DataLayout, DataLayout]] = {}
        for name in graph.topo_order():
            preds = graph.preds(name)
            din = out_dl[preds[0]] if preds else new[name][0]
            final[name] = (din, out_dl[name])
        return final


# -- final evaluation with the Data-Scheduler ----------------------------------


def _node_of(lm: LM, region: Region, na_col: int,
             idx: dict[str, tuple[int, int]]) -> int:
    st = loop_strides(lm)
    h = region.h_pos
    w = region.w_pos
    for l in LOOPS:
        ih, iw = idx.get(l, (0, 0))
        sh, sw = st[l]
        h += ih * sh
        w += iw * sw
    return h * na_col + w


def _enumerate_indices(lm: LM, loops: tuple[str, ...]):
    """All index dicts over the given loops (others zero)."""
    outs = [dict()]
    for l in loops:
        i = LOOPS.index(l)
        new = []
        for a in range(lm.ph[i]):
            for b in range(lm.pw[i]):
                for d in outs:
                    dd = dict(d)
                    dd[l] = (a, b)
                    new.append(dd)
        outs = new
    return outs


def _sharing_problem_list(lm: LM, region_shape: tuple[int, int], wr: int,
                          w_bytes: float, i_bytes: float, p_bytes: float
                          ) -> list[tuple[tuple[tuple[int, ...], ...], float]]:
    """A layer's three sharing processes as ``(sets, chunk)`` problems.

    Each entry is one joint min-max-link-load solve on the region's mesh
    (sets of size <= 1 and zero-byte chunks already dropped) — the shared
    construction behind both the per-layer :func:`_sharing_latency` path
    and the whole-mapping batched ``engine.scheduler_opt.schedule_many``
    prefill.
    """
    na_col = region_shape[1]
    region = Region(0, 0, region_shape[0], region_shape[1])
    problems: list[tuple[tuple[tuple[int, ...], ...], float]] = []

    def add(sets: list[list[int]], chunk: float):
        kept = tuple(tuple(s) for s in sets if len(s) > 1)
        if kept and chunk > 0:
            problems.append((kept, chunk))

    # weight sharing: per (k, c) group split into wr replica subsets
    n_ws = lm.weight_share
    group = math.ceil(n_ws / max(1, min(wr, n_ws)))
    if group > 1 and w_bytes > 0:
        share_loops = tuple(l for l in ("B", "P", "Q") if lm.parts(l) > 1)
        sets = []
        for idx in _enumerate_indices(lm, tuple(
                l for l in ("K", "C") if lm.parts(l) > 1)):
            nodes = [_node_of(lm, region, na_col, {**idx, **sub})
                     for sub in _enumerate_indices(lm, share_loops)]
            for s in range(0, len(nodes), group):
                sets.append(nodes[s:s + group])
        add(sets, w_bytes / group)
    # input sharing across K
    if lm.input_share > 1 and i_bytes > 0:
        other = tuple(l for l in ("B", "P", "Q", "C") if lm.parts(l) > 1)
        sets = []
        for idx in _enumerate_indices(lm, other):
            nodes = [_node_of(lm, region, na_col, {**idx, **sub})
                     for sub in _enumerate_indices(lm, ("K",))]
            sets.append(nodes)
        add(sets, i_bytes / lm.input_share)
    # psum reduction across C (~2 ring passes)
    if lm.psum_share > 1 and p_bytes > 0:
        other = tuple(l for l in ("B", "P", "Q", "K") if lm.parts(l) > 1)
        sets = []
        for idx in _enumerate_indices(lm, other):
            nodes = [_node_of(lm, region, na_col, {**idx, **sub})
                     for sub in _enumerate_indices(lm, ("C",))]
            sets.append(nodes)
        add(sets, 2 * p_bytes / lm.psum_share)
    return problems


_SCHED_MEMO = _BoundedCache(_CACHE_SCHEDULES)


def _sched_key(hw: HwConfig, lm: LM, region_shape: tuple[int, int], wr: int,
               w_bytes: float, i_bytes: float, p_bytes: float, solver: str,
               seed: int, backend: str) -> tuple:
    # tsp/shp ignore the LS backend: normalize so they share one entry
    return (hw, lm, region_shape, wr, w_bytes, i_bytes, p_bytes, solver,
            seed, backend if solver == "ilp" else "-")


def _sharing_latency(hw: HwConfig, lm: LM, region_shape: tuple[int, int],
                     wr: int, w_bytes: float, i_bytes: float, p_bytes: float,
                     solver: str, seed: int,
                     backend: str = "scan") -> tuple[float, float]:
    """Scheduled (latency_s, energy_pj) for a layer's three sharing processes.

    Translation-invariant (XY routes stay inside the set's bounding box), so
    memoized on the region *shape*, not its position.  The memo is a plain
    :class:`_BoundedCache` (rather than an ``lru_cache``) so the batched
    ``evaluate_mapping`` path can prefill whole mappings through
    ``engine.scheduler_opt.schedule_many`` — per-problem PRNG streams make
    the prefilled values bit-identical to this per-layer path.
    """
    key = _sched_key(hw, lm, region_shape, wr, w_bytes, i_bytes, p_bytes,
                     solver, seed, backend)
    got = _SCHED_MEMO.get(key)
    if got is not None:
        return got
    noc = MeshNoc(region_shape[0], region_shape[1])
    solve = SOLVERS[solver]
    lat = 0.0
    en = 0.0
    for sets, chunk in _sharing_problem_list(lm, region_shape, wr, w_bytes,
                                             i_bytes, p_bytes):
        # every solver draws from an explicit Random(seed): repeated DSE
        # runs over the same mapping are bit-reproducible
        res = solve(noc, [list(s) for s in sets], [chunk] * len(sets),
                    hw.link_bw_bytes, hw.cons.freq_hz,
                    hw.cons.noc_energy_pj_per_bit_hop, seed=seed,
                    backend=backend)
        lat += res.latency_s
        en += res.energy_pj
    out = (lat, en)
    _SCHED_MEMO.put(key, out)
    return out


def _sched_cache_info():
    from types import SimpleNamespace
    return SimpleNamespace(currsize=len(_SCHED_MEMO._d),
                           maxsize=_SCHED_MEMO.maxsize)


# lru_cache-compatible handles (tests and clear_mapper_caches use them)
_sharing_latency.cache_clear = _SCHED_MEMO.clear
_sharing_latency.cache_info = _sched_cache_info


def _layer_sharing_args(mapping: Mapping, lname: str):
    """(lm, region_shape, wr, w/i/p bytes) of one mapped heavy layer."""
    hw = mapping.hw
    ch = mapping.choices[lname]
    pl = part_layer(mapping.graph.layer(lname), ch.lm)
    dbytes = hw.cons.data_bits // 8
    return (ch.lm, (ch.region.h_shape, ch.region.w_shape), ch.wr,
            pl.weight_count * dbytes, pl.ifmap_count * dbytes,
            pl.ofmap_count * (hw.cons.psum_bits // 8))


def _prefill_schedules(mapping: Mapping, solver: str, seed: int,
                       backend: str) -> None:
    """Solve a whole mapping's missing sharing problems in one engine batch.

    The single-mapping entry point of :func:`prefill_schedules_many`
    (``evaluate_mapping`` calls it per mapping on the scan backend).
    """
    prefill_schedules_many([mapping], solver=solver, seed=seed,
                           backend=backend)


def prefill_schedules_many(mappings: Sequence[Mapping], *,
                           solver: str = "ilp", seed: int = 0,
                           backend: str = "scan") -> None:
    """Prefill the sharing-schedule memo for SEVERAL mappings in one batch.

    The cross-config generalization behind the device-resident DSE
    pipeline: collects every uncached ``_sharing_latency`` key across all
    mappings (typically one mapping per still-feasible config of a proposal
    round), dedups the underlying ``(mesh, sets, chunk)`` problems, and
    runs ONE :func:`engine.scheduler_opt.schedule_many` call per distinct
    ``(link_bw, freq, pj/bit/hop)`` NoC-scalar group — configs that differ
    only in parameters the NoC scalars don't depend on share a single
    pow2-bucketed dispatch.  Every memo value is bit-identical to the
    serial per-layer path (``schedule_many``'s per-problem PRNG streams
    are batch-independent), so prefilled and lazily-computed entries can
    never disagree.  No-op for non-scan backends / non-ilp solvers.
    """
    if solver != "ilp" or backend != "scan":
        return
    from ..engine.scheduler_opt import schedule_many

    def _scalars(hw: HwConfig) -> tuple:
        return (hw.link_bw_bytes, hw.cons.freq_hz,
                hw.cons.noc_energy_pj_per_bit_hop)

    with trace.span("sched_problems", cat="engine"):
        # sched key -> (shape, problems, hw); the key embeds hw, so
        # identical sharing problems under DIFFERENT configs stay distinct
        # memo entries
        want: dict[tuple, tuple] = {}
        for mapping in mappings:
            hw = mapping.hw
            for lname in mapping.choices:
                args = _layer_sharing_args(mapping, lname)
                key = _sched_key(hw, *args, solver, seed, backend)
                if key in _SCHED_MEMO or key in want:
                    continue
                want[key] = (args[1], _sharing_problem_list(*args), hw)
        if not want:
            return
        # NoC-scalar triple -> (problem identity -> flat index, problems)
        groups: dict[tuple, tuple[dict, list]] = {}
        for shape, problems, hw in want.values():
            uniq, flat = groups.setdefault(_scalars(hw), ({}, []))
            for sets, chunk in problems:
                pk = (shape, sets, chunk)
                if pk not in uniq:
                    uniq[pk] = len(flat)
                    flat.append((MeshNoc(shape[0], shape[1]), sets,
                                 [chunk] * len(sets)))
    with trace.span("prefill_schedules", cat="engine",
                    mappings=len(mappings), missing=len(want),
                    problems=sum(len(f) for _, f in groups.values()),
                    groups=len(groups)):
        solved = {tri: schedule_many(flat, *tri, seed=seed)
                  for tri, (_, flat) in groups.items()}
    fills = []
    for key, (shape, problems, hw) in want.items():
        uniq, _ = groups[_scalars(hw)]
        results = solved[_scalars(hw)]
        lat = 0.0
        en = 0.0
        for sets, chunk in problems:
            res = results[uniq[(shape, sets, chunk)]]
            lat += res.latency_s
            en += res.energy_pj
        fills.append((key, (lat, en)))
    _SCHED_MEMO.put_many(fills)


def evaluate_mapping(mapping: Mapping, *, solver: str = "ilp",
                     seed: int = 0,
                     scheduler_backend: str = "scan") -> EvalReport:
    """Final latency/energy with Data-Scheduler-optimized data sharing.

    ``scheduler_backend`` picks the joint-LS implementation behind the
    ``"ilp"`` solver: ``"scan"`` (default) batches every uncached layer's
    sharing problems through the jitted engine scheduler in one
    ``schedule_many`` call before the per-layer accounting walk;
    ``"loop"`` keeps the host-Python reference search.
    """
    g = mapping.graph
    hw = mapping.hw
    dbytes = hw.cons.data_bits // 8
    if scheduler_backend == "scan" and solver == "ilp":
        _prefill_schedules(mapping, solver, seed, scheduler_backend)
    layers: list[LayerReport] = []
    total_lat = 0.0
    total_energy = 0.0
    bd = {"mac": 0.0, "sram": 0.0, "dram": 0.0, "noc": 0.0}
    for seg_i, seg in enumerate(mapping.segments):
        sm = mapping.sm.get(seg_i)
        region_lat: dict[int, float] = {}
        for bi, branch in enumerate(seg.branches):
            for lname in branch.heavy_layers(g):
                ch = mapping.choices.get(lname)
                if ch is None:
                    continue
                layer = g.layer(lname)
                pl = part_layer(layer, ch.lm)
                node = part_layer_cost(hw, pl, ch.dl_in, ch.dl_out)
                w_kc = pl.weight_count * dbytes
                i_b = pl.ifmap_count * dbytes
                p_b = pl.ofmap_count * (hw.cons.psum_bits // 8)
                comm_lat, comm_en = _sharing_latency(
                    hw, ch.lm, (ch.region.h_shape, ch.region.w_shape),
                    ch.wr, w_kc, i_b, p_b, solver, seed,
                    backend=scheduler_backend)
                n_nodes = ch.region.n_nodes
                lat = node.latency_s + comm_lat
                energy = node.energy_pj * n_nodes + comm_en
                ri = sm.ir[bi] if sm else 0
                region_lat[ri] = region_lat.get(ri, 0.0) + lat
                bd["mac"] += node.e_mac_pj * n_nodes
                bd["sram"] += node.e_sram_pj * n_nodes
                bd["dram"] += node.e_dram_pj * n_nodes
                bd["noc"] += comm_en
                total_energy += energy
                layers.append(LayerReport(lname, lat, comm_lat, energy,
                                          comm_en, dict(node.breakdown)))
        total_lat += max(region_lat.values()) if region_lat else 0.0
    return EvalReport(total_lat, total_energy, bd, layers)
