"""PIM-Mapper reference (NicePIM Sec. VI, Algorithms 1 and 2).

Per segment, slicing-tree segment mappings (SM) assign branches to
rectangular regions; per layer and region, every layer partition (LM) is
costed with every weight-replication (WR) value and the best LM per WR
kept; a knapsack DP per region and a min-plus combination across segments
pick one candidate per layer under the per-node DRAM capacity; the
alternated data-layout (DL) pass then picks each layer's output layout.
One candidate at a time, on the host, in the dtype given.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from functools import lru_cache

import numpy as np

from .model import DL, Graph, Hw, Layer, layouts, node_cost

LOOPS = ("B", "P", "Q", "K", "C")
ORDERS = (("B", "P", "Q", "K", "C"), ("K", "C", "B", "P", "Q"),
          ("B", "K", "P", "Q", "C"), ("P", "Q", "B", "C", "K"),
          ("C", "K", "Q", "P", "B"))
INF = float("inf")


# -- partitions ------------------------------------------------------------------


@dataclass(frozen=True)
class LM:
    ph: tuple
    pw: tuple
    p_order: tuple

    def parts(self, loop: str) -> int:
        i = LOOPS.index(loop)
        return self.ph[i] * self.pw[i]

    @property
    def n_nodes(self) -> int:
        return math.prod(self.ph) * math.prod(self.pw)

    @property
    def weight_share(self) -> int:
        return self.parts("B") * self.parts("P") * self.parts("Q")


@lru_cache(maxsize=None)
def _splits(n: int, k: int):
    if k == 1:
        return ((n,),)
    return tuple((d,) + rest for d in range(1, n + 1) if n % d == 0
                 for rest in _splits(n // d, k - 1))


@lru_cache(maxsize=None)
def enumerate_lms(layer: Layer, h: int, w: int, cap: int) -> tuple:
    lens = {"B": layer.B, "P": layer.P, "Q": layer.Q, "K": layer.K,
            "C": layer.C}
    out = []
    for ph in _splits(h, 5):
        for pw in _splits(w, 5):
            if not all(ph[i] * pw[i] <= lens[l] or ph[i] * pw[i] == 1
                       for i, l in enumerate(LOOPS)):
                continue
            out.extend(LM(ph, pw, od) for od in ORDERS)
    if len(out) > cap:
        def ragged(lm):
            r = 0.0
            for i, l in enumerate(LOOPS):
                p = lm.ph[i] * lm.pw[i]
                r += (math.ceil(lens[l] / p) * p / max(1, lens[l])) - 1.0
            return r
        out.sort(key=ragged)
        out = out[:cap]
    return tuple(out)


@lru_cache(maxsize=None)
def part_layer(layer: Layer, lm: LM) -> Layer:
    Bp = math.ceil(layer.B / lm.parts("B"))
    Pp = math.ceil(layer.P / lm.parts("P"))
    Qp = math.ceil(layer.Q / lm.parts("Q"))
    Kp = math.ceil(layer.K / lm.parts("K"))
    Cp = math.ceil(layer.C / lm.parts("C"))
    return replace(layer, B=Bp, C=Cp, H=(Pp - 1) * layer.stride + layer.HK,
                   W=(Qp - 1) * layer.stride + layer.WK, K=Kp, pad=0)


def wr_candidates(lm: LM, n: int) -> list[int]:
    out, v = [], lm.weight_share
    while v >= 1 and len(out) < n:
        out.append(v)
        if v == 1:
            break
        v = max(1, v // 2)
    if 1 not in out:
        out.append(1)
    return out


def loop_strides(lm: LM) -> dict:
    order = lm.p_order
    hr = [lm.ph[LOOPS.index(l)] for l in order]
    wr = [lm.pw[LOOPS.index(l)] for l in order]

    def strides(rad):
        out = [1] * len(rad)
        for i in range(len(rad) - 2, -1, -1):
            out[i] = out[i + 1] * rad[i + 1]
        return out
    hs, ws = strides(hr), strides(wr)
    return {l: (hs[i], ws[i]) for i, l in enumerate(order)}


def group_coords(lm: LM, loops: tuple) -> list:
    st = loop_strides(lm)
    coords = [(0, 0)]
    for l in loops:
        i = LOOPS.index(l)
        sh, sw = st[l]
        coords = [(h + a * sh, w + b * sw) for a in range(lm.ph[i])
                  for b in range(lm.pw[i]) for (h, w) in coords]
    coords.sort(key=lambda c: (c[0], c[1] if c[0] % 2 == 0 else -c[1]))
    return coords


def ring_hops(coords) -> float:
    n = len(coords)
    if n <= 1:
        return 0.0
    d = sum(abs(coords[i][0] - coords[(i + 1) % n][0])
            + abs(coords[i][1] - coords[(i + 1) % n][1]) for i in range(n))
    return d / n


def _ring(n, total, hops, hw: Hw, dt):
    if n <= 1 or total <= 0:
        return dt(0), dt(0)
    chunk = dt(total) / dt(n)
    hop = max(dt(1), dt(hops))
    lat = dt(n - 1) * chunk * hop / dt(hw.link_bw_bytes)
    en = (dt(n - 1) * dt(total) * dt(8) * hop
          * dt(hw.c("noc_energy_pj_per_bit_hop")))
    return lat, en


def comm_estimate(layer: Layer, lm: LM, wr: int, hw: Hw, dt):
    """Analytic ring estimate: (latency, energy, weight bytes per node)."""
    dbytes = hw.c("data_bits") // 8
    pl = part_layer(layer, lm)
    lat = en = dt(0)
    n_ws = lm.weight_share
    wr = max(1, min(wr, n_ws))
    group = math.ceil(n_ws / wr)
    w_kc = pl.weight_count * dbytes
    stored = dt(w_kc) / dt(group)
    if group > 1:
        loops = tuple(l for l in ("B", "P", "Q") if lm.parts(l) > 1)
        l1, e1 = _ring(group, w_kc, ring_hops(group_coords(lm, loops)[:group]),
                       hw, dt)
        lat += l1
        en += e1 * dt(lm.parts("K") * lm.parts("C") * wr)
    if lm.parts("K") > 1:
        l2, e2 = _ring(lm.parts("K"), pl.ifmap_count * dbytes,
                       ring_hops(group_coords(lm, ("K",))), hw, dt)
        lat += l2
        en += e2 * dt(n_ws * lm.parts("C"))
    if lm.parts("C") > 1:
        l3, e3 = _ring(lm.parts("C"),
                       2 * pl.ofmap_count * (hw.c("psum_bits") // 8),
                       ring_hops(group_coords(lm, ("C",))), hw, dt)
        lat += l3
        en += e3 * dt(n_ws * lm.parts("K"))
    return lat, en, stored


# -- segment mappings -----------------------------------------------------------


@dataclass(frozen=True)
class Region:
    h_pos: int
    w_pos: int
    h_shape: int
    w_shape: int


@dataclass(frozen=True)
class SM:
    regions: tuple
    ir: tuple

    def branches_of(self, r: int) -> list[int]:
        return [b for b, x in enumerate(self.ir) if x == r]


def _lpt(loads, n_bins):
    order = sorted(range(len(loads)), key=lambda i: -loads[i])
    bins, out = [0.0] * n_bins, [0] * len(loads)
    for i in order:
        b = min(range(n_bins), key=lambda j: bins[j])
        out[i] = b
        bins[b] += loads[i]
    return out


def _slice(rect, loads, idxs, out):
    h0, w0, hs, ws = rect
    if len(idxs) == 1:
        out[idxs[0]] = Region(h0, w0, hs, ws)
        return
    half = len(idxs) // 2
    a, b = idxs[:half], idxs[half:]
    la, lb = sum(loads[i] for i in a), sum(loads[i] for i in b)
    frac = la / max(1e-12, la + lb)
    if hs >= ws:
        cut = min(hs - 1, max(1, round(hs * frac)))
        _slice((h0, w0, cut, ws), loads, a, out)
        _slice((h0 + cut, w0, hs - cut, ws), loads, b, out)
    else:
        cut = min(ws - 1, max(1, round(ws * frac)))
        _slice((h0, w0, hs, cut), loads, a, out)
        _slice((h0, w0 + cut, hs, ws - cut), loads, b, out)


def sm_candidates(g: Graph, branches, na_row: int, na_col: int) -> list[SM]:
    loads = [max(1.0, float(sum(g.layer[n].macs for n in b)))
             for b in branches]
    cap = min(len(branches), na_row * na_col)
    n_regs, v = [], 1
    while v < cap:
        n_regs.append(v)
        v *= 2
    n_regs.append(cap)
    out, seen = [], set()
    for n_reg in n_regs:
        ir = _lpt(loads, n_reg)
        remap = {r: i for i, r in enumerate(sorted(set(ir)))}
        ir = [remap[r] for r in ir]
        reg_loads = [0.0] * len(remap)
        for b, r in enumerate(ir):
            reg_loads[r] += loads[b]
        regions: dict = {}
        _slice((0, 0, na_row, na_col), reg_loads, list(range(len(remap))),
               regions)
        sm = SM(tuple(regions[i] for i in range(len(remap))), tuple(ir))
        if (sm.regions, sm.ir) not in seen:
            seen.add((sm.regions, sm.ir))
            out.append(sm)
    return out


# -- Algorithm 2: capacity DP ---------------------------------------------------


class _RegionTable:
    def __init__(self, layer_cands, units: int, unit_bytes: float, dt):
        self.layer_cands = layer_cands
        perf = np.zeros(units + 1, dtype=dt)
        self.choice = np.full((len(layer_cands), units + 1), -1, np.int64)
        self.eff = np.zeros((len(layer_cands), units + 1), np.int64)
        self.sizes = []
        caps = np.arange(units + 1)
        for li, (_, cands) in enumerate(layer_cands):
            sizes = np.minimum(units + 1, np.ceil(
                np.array([c[2] for c in cands], dtype=np.float64)
                / unit_bytes)).astype(np.int64)
            self.sizes.append(sizes)
            if not cands:
                nperf = np.full(units + 1, INF, dtype=dt)
            else:
                perfs = np.array([c[1] for c in cands], dtype=dt)
                left = caps[None, :] - sizes[:, None]
                scores = np.where(left >= 0,
                                  perf[np.clip(left, 0, units)]
                                  + perfs[:, None], dt(INF))
                nperf = scores.min(axis=0)
                self.choice[li] = np.where(np.isfinite(nperf),
                                           scores.argmin(axis=0), -1)
            run = np.minimum.accumulate(nperf)
            last = np.where(~(nperf > run), np.arange(units + 1), 0)
            self.eff[li] = np.maximum.accumulate(last)
            perf = run
        self.perf = perf

    def backtrack(self, cap: int) -> dict:
        picks = {}
        for li in range(len(self.layer_cands) - 1, -1, -1):
            name, cands = self.layer_cands[li]
            eff = int(self.eff[li, cap])
            ci = int(self.choice[li, eff])
            if ci < 0:
                if cands:
                    picks[name] = min(range(len(cands)),
                                      key=lambda i: cands[i][1])
                continue
            picks[name] = ci
            cap = eff - int(self.sizes[li][ci])
        return picks


@dataclass
class Choice:
    lm: LM
    wr: int
    dl_in: DL
    dl_out: DL
    region: Region
    perf_s: float
    size_bytes: float


@dataclass
class Mapping:
    sm: dict
    choices: dict
    est_latency_s: float


def _default_dl(channels: int) -> DL:
    g = 1
    while g * 2 <= min(channels, 16):
        g *= 2
    return DL("BCHW", g)


class Mapper:
    """Algorithm 1 for one design point, in dtype ``dt``."""

    def __init__(self, hw: Hw, *, max_optim_iter: int, lm_cap: int,
                 n_wr: int, cap_units: int = 1024, dl_max_group: int = 32,
                 dt=np.float64):
        self.hw = hw
        self.iters = max_optim_iter
        self.lm_cap = lm_cap
        self.n_wr = n_wr
        self.units = cap_units
        self.max_group = dl_max_group
        self.dt = dt
        self._cands: dict = {}

    def candidates(self, layer: Layer, h: int, w: int, din: DL, dout: DL):
        """Per-WR best LM of a layer on an ``h x w`` region, size-sorted."""
        key = (layer, h, w, din, dout)
        got = self._cands.get(key)
        if got is not None:
            return got
        best: dict = {}
        for lm in enumerate_lms(layer, h, w, self.lm_cap):
            node = node_cost(self.hw, part_layer(layer, lm), din, dout,
                             self.dt)
            for wr in wr_candidates(lm, self.n_wr):
                c_lat, _, stored = comm_estimate(layer, lm, wr, self.hw,
                                                 self.dt)
                perf = self.dt(node.latency_s) + c_lat
                cur = best.get(wr)
                if cur is None or perf < cur[0]:
                    best[wr] = (perf, stored, lm)
        out = [(wr, p, s, lm) for wr, (p, s, lm) in best.items()]
        out.sort(key=lambda t: -t[2])
        self._cands[key] = out
        return out

    def map(self, g: Graph) -> Mapping:
        segs = g.segments()
        dls = {n: (_default_dl(g.layer[n].C), _default_dl(g.layer[n].K))
               for n in g.order}
        mapping = None
        for _ in range(self.iters):
            mapping = self._solve(g, segs, dls)
            dls = self._optimize_dl(g, mapping)
            for name, ch in mapping.choices.items():
                ch.dl_in, ch.dl_out = dls[name]
        return mapping

    def _solve(self, g: Graph, segs, dls) -> Mapping:
        dt, units = self.dt, self.units
        unit_bytes = self.hw.node_dram_capacity / units
        seg_tables = []
        for seg in segs:
            per_sm = []
            for sm in sm_candidates(g, seg.branches, self.hw.na_row,
                                    self.hw.na_col):
                reg_tabs = []
                seg_perf = np.zeros(units + 1, dtype=dt)
                for ri, region in enumerate(sm.regions):
                    lc = []
                    for bi in sm.branches_of(ri):
                        for name in g.heavy(seg.branches[bi]):
                            din, dout = dls[name]
                            lc.append((name, self.candidates(
                                g.layer[name], region.h_shape,
                                region.w_shape, din, dout)))
                    if not lc:
                        continue
                    tab = _RegionTable(lc, units, unit_bytes, dt)
                    seg_perf = np.maximum(seg_perf, tab.perf)
                    reg_tabs.append((region, tab))
                if np.isinf(seg_perf[units]) and reg_tabs:
                    continue
                per_sm.append((sm, seg_perf, reg_tabs))
            if any(g.heavy(b) for b in seg.branches) and not per_sm:
                raise RuntimeError("no feasible mapping under DRAM capacity")
            seg_tables.append(per_sm)
        tab = np.zeros(units + 1, dtype=dt)
        seg_choice = []
        for per_sm in seg_tables:
            if not per_sm:
                seg_choice.append(None)
                continue
            best = np.full(units + 1, INF, dtype=dt)
            best_sm = np.full(units + 1, -1, np.int64)
            for smi, (_, seg_perf, _) in enumerate(per_sm):
                better = seg_perf < best
                best = np.where(better, seg_perf, best)
                best_sm[better] = smi
            ext = np.concatenate([np.full(units, INF, dtype=dt), best])
            rows = np.lib.stride_tricks.sliding_window_view(
                ext, units + 1)[:, ::-1]
            scores = tab[None, :] + rows
            idx = scores.argmin(axis=1)
            ntab = scores[np.arange(units + 1), idx]
            arg = np.where(np.isfinite(ntab), idx, -1)
            tab = np.minimum.accumulate(ntab)
            src = np.maximum.accumulate(
                np.where(ntab <= tab, np.arange(units + 1), 0))
            seg_choice.append((best_sm, arg[src]))
        if not np.isfinite(tab[units]):
            raise RuntimeError("no feasible mapping under DRAM capacity")
        plan, cap = [], units
        for si in range(len(seg_tables) - 1, -1, -1):
            if seg_choice[si] is None:
                continue
            best_sm, arg = seg_choice[si]
            i = max(0, int(arg[cap]))
            cap_seg = cap - i
            plan.append((si, int(best_sm[min(cap_seg, units)]), cap_seg))
            cap = i
        choices, sms = {}, {}
        for si, smi, cap_seg in reversed(plan):
            per_sm = seg_tables[si]
            sm, _, reg_tabs = per_sm[smi if smi >= 0 else 0]
            sms[si] = sm
            for region, rtab in reg_tabs:
                pick = rtab.backtrack(min(cap_seg, units))
                for name, cands in rtab.layer_cands:
                    if not cands:
                        continue
                    wr, p, size, lm = cands[pick.get(name, 0)]
                    din, dout = dls[name]
                    choices[name] = Choice(lm, wr, din, dout, region,
                                           float(p), float(size))
        return Mapping(sms, choices, float(tab[units]))

    def _optimize_dl(self, g: Graph, mapping: Mapping) -> dict:
        new, out_dl = {}, {}
        for name in g.topo():
            layer = g.layer[name]
            preds = g.preds[name]
            if preds:
                din = out_dl[preds[0]]
                for p in preds[1:]:
                    out_dl[p] = din
            else:
                din = _default_dl(layer.C)
            if layer.heavy and name in mapping.choices:
                pl = part_layer(layer, mapping.choices[name].lm)
                best, best_lat = None, INF
                for cand in layouts(layer.K, self.max_group):
                    lat = node_cost(self.hw, pl, din, cand, self.dt).latency_s
                    if lat < best_lat:
                        best, best_lat = cand, lat
                out_dl[name] = best
            else:
                out_dl[name] = din
            new[name] = (din, out_dl[name])
        return {n: (out_dl[g.preds[n][0]] if g.preds[n] else new[n][0],
                    out_dl[n]) for n in g.topo()}
