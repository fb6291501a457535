"""DNN graph, hardware, data layouts and the per-node cost model.

Follows NicePIM (arXiv:2305.19041) Sec. II-B (conv loop nest), Sec. III-B
(segments and branches), Sec. III-E (DRAM data layouts) and Table II (the
substrate constants), with the analytic stand-in for Timeloop/Accelergy:
double-buffered SRAM tilings under the buffer capacities, latency
``max(compute, DRAM)`` per tiling, energies at the chosen tiling.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import lru_cache

import numpy as np

HEAVY_KINDS = ("conv", "matmul", "dwconv")

MAC_ENERGY_PJ = 0.30
SRAM_BASE_PJ_PER_BIT = 0.05
SRAM_LOG_PJ_PER_BIT = 0.012


# -- graph ---------------------------------------------------------------------


@dataclass(frozen=True)
class Layer:
    name: str
    kind: str
    B: int = 1
    C: int = 1
    H: int = 1
    W: int = 1
    K: int = 1
    HK: int = 1
    WK: int = 1
    stride: int = 1
    pad: int = 0

    @property
    def heavy(self) -> bool:
        return self.kind in HEAVY_KINDS

    @property
    def P(self) -> int:
        if not self.heavy:
            return self.H
        return max(1, (self.H + 2 * self.pad - self.HK) // self.stride + 1)

    @property
    def Q(self) -> int:
        if not self.heavy:
            return self.W
        return max(1, (self.W + 2 * self.pad - self.WK) // self.stride + 1)

    @property
    def macs(self) -> int:
        if not self.heavy:
            return 0
        if self.kind == "dwconv":
            return self.B * self.K * self.P * self.Q * self.HK * self.WK
        return self.B * self.K * self.C * self.P * self.Q * self.HK * self.WK

    @property
    def weight_count(self) -> int:
        if not self.heavy:
            return 0
        if self.kind == "dwconv":
            return self.K * self.HK * self.WK
        return self.K * self.C * self.HK * self.WK

    @property
    def ifmap_count(self) -> int:
        return self.B * self.C * self.H * self.W

    @property
    def ofmap_count(self) -> int:
        return self.B * self.K * self.P * self.Q


@dataclass
class Segment:
    index: int
    branches: list[list[str]]


class Graph:
    """A DAG of layers cut into serial segments of parallel branches."""

    def __init__(self, name: str, layers: list[Layer],
                 preds: dict[str, list[str]]):
        self.name = name
        self.order = [l.name for l in layers]
        self.layer = {l.name: l for l in layers}
        self.preds = {n: list(preds.get(n, ())) for n in self.order}
        self.succs: dict[str, list[str]] = {n: [] for n in self.order}
        for n in self.order:
            for p in self.preds[n]:
                self.succs[p].append(n)

    @classmethod
    def from_config(cls, graph: dict) -> "Graph":
        layers, preds = [], {}
        for row in graph["layers"]:
            kw = {k: row[k] for k in ("B", "C", "H", "W", "K", "HK", "WK",
                                      "stride", "pad")}
            layers.append(Layer(row["name"], row["kind"], **kw))
            preds[row["name"]] = list(row["preds"])
        return cls(graph["name"], layers, preds)

    def heavy(self, names) -> list[str]:
        return [n for n in names if self.layer[n].heavy]

    def topo(self) -> list[str]:
        indeg = {n: len(self.preds[n]) for n in self.order}
        ready = [n for n in self.order if indeg[n] == 0]
        out = []
        while ready:
            n = ready.pop(0)
            out.append(n)
            for s in self.succs[n]:
                indeg[s] -= 1
                if indeg[s] == 0:
                    ready.append(s)
        return out

    def segments(self) -> list[Segment]:
        topo = self.topo()
        pos = {n: i for i, n in enumerate(topo)}
        cuts, open_edges = set(), 0
        for v in topo:
            open_edges -= len(self.preds[v])
            if open_edges == 0:
                cuts.add(v)
            open_edges += len(self.succs[v])
        segs, cur = [], []
        for v in topo:
            cur.append(v)
            if v in cuts:
                segs.append(Segment(len(segs), self._branches(cur, pos)))
                cur = []
        if cur:
            segs.append(Segment(len(segs), self._branches(cur, pos)))
        return segs

    def _branches(self, nodes, pos) -> list[list[str]]:
        inside = set(nodes)
        parent = {n: n for n in nodes}

        def find(x):
            while parent[x] != x:
                x = parent[x]
            return x

        merges = [n for n in nodes
                  if sum(p in inside for p in self.preds[n]) > 1]
        merge_set = set(merges)
        for n in nodes:
            if n in merge_set:
                continue
            for p in self.preds[n]:
                if p in inside and p not in merge_set:
                    ra, rb = find(n), find(p)
                    if ra != rb:
                        parent[ra] = rb
        groups: dict[str, list[str]] = {}
        for n in nodes:
            if n not in merge_set:
                groups.setdefault(find(n), []).append(n)
        for m in merges:
            ins = [p for p in self.preds[m]
                   if p in inside and p not in merge_set]
            if ins:
                groups.setdefault(find(ins[0]), []).append(m)
            else:
                groups[m] = [m]
        out = [sorted(g, key=lambda n: pos[n]) for g in groups.values()]
        out.sort(key=lambda b: pos[b[0]])
        return out


# -- hardware ------------------------------------------------------------------


@dataclass(frozen=True)
class Hw:
    """One Table-II design point plus the substrate constants."""

    na_row: int
    na_col: int
    pea_row: int
    pea_col: int
    ibuf_kib: int
    wbuf_kib: int
    obuf_kib: int
    cons: tuple  # sorted (name, value) pairs of the constants

    @classmethod
    def make(cls, values, constants: dict) -> "Hw":
        return cls(*(int(v) for v in values),
                   cons=tuple(sorted(constants.items())))

    def c(self, name):
        return dict(self.cons)[name]

    @property
    def n_nodes(self) -> int:
        return self.na_row * self.na_col

    @property
    def banks_per_node(self) -> int:
        return self.c("ba_row") * self.c("ba_col") // self.n_nodes

    @property
    def node_dram_capacity(self) -> int:
        return self.banks_per_node * self.c("cap_bank_bytes")

    @property
    def node_dram_width_bits(self) -> int:
        return self.banks_per_node * self.c("width_bank_bits")

    @property
    def link_bw_bytes(self) -> float:
        flit = max(32, self.node_dram_width_bits // 2)
        return flit / 8 * self.c("freq_hz")


def area_mm2(values, constants: dict, dt=np.float64):
    """Logic-die area of ``[n, 7]`` design points (MAC arrays + SRAM)."""
    t = np.asarray(values, dtype=dt)
    na = t[:, 0] * t[:, 1]
    pe = t[:, 2] * t[:, 3] * dt(constants["mac_area_um2"]) * dt(1e-6)
    buf_mib = (t[:, 4] + t[:, 5] + t[:, 6]) / dt(1024)
    return na * (pe + buf_mib * dt(constants["sram_area_mm2_per_mib"])
                 + dt(constants["node_fixed_area_mm2"]))


# -- data layouts ---------------------------------------------------------------


@dataclass(frozen=True)
class DL:
    order: str = "BCHW"
    group: int = 1


def layouts(C: int, max_group: int = 32) -> list[DL]:
    out, g = [DL("BHWC")], 1
    while g <= min(C, max_group):
        out.append(DL("BCHW", g))
        g *= 2
    return out


def _mean_bursts(run, align, burst, dt):
    g = math.gcd(max(1, int(align)), int(burst))
    m = dt(burst // g)
    b = dt(burst)
    q = np.ceil(run / b) - dt(1)
    r = run - q * b
    over = m - dt(1) - np.floor((b - r) / dt(g))
    return q + dt(1) + over / m


def _tile_fetch(fmap, tb, tc, th, tw, dl: DL, burst, row_words, dt):
    """(bursts, row activations) of one tile fetch under a layout."""
    B, C, H, W = (dt(v) for v in fmap)
    tb, tc = np.minimum(tb, B), np.minimum(tc, C)
    th, tw = np.minimum(th, H), np.minimum(tw, W)
    full_w, full_h, full_c = tw >= W, th >= H, tc >= C
    one = dt(1)
    if dl.order == "BHWC":
        run = np.where(full_c, tw * C, tc)
        n_runs = np.where(full_c, tb * th, tb * th * tw)
        run = np.where(full_c & full_w, th * W * C, run)
        n_runs = np.where(full_c & full_w, tb, n_runs)
        whole = full_c & full_w & full_h
        run = np.where(whole, tb * H * W * C, run)
        n_runs = np.where(whole, one, n_runs)
        span = np.where(whole, tb * H * W * C, ((th - one) * W + tw) * C)
        n_ext = np.where(whole, one, tb)
        align = int(fmap[1])
    else:
        g = dt(min(max(1, dl.group), int(fmap[1])))
        cg = np.ceil(tc / g)
        run = tw * g * np.ones_like(tc)
        n_runs = tb * cg * th
        run = np.where(full_w, tw * g * th, run)
        n_runs = np.where(full_w, tb * cg, n_runs)
        plane = full_w & full_h
        run = np.where(plane, H * W * g * cg, run)
        n_runs = np.where(plane, tb, n_runs)
        whole = plane & full_c
        run = np.where(whole, tb * C * H * W, run)
        n_runs = np.where(whole, one, n_runs)
        span = np.where(plane, run, ((th - one) * W + tw) * g)
        n_ext = np.where(plane, n_runs, tb * cg)
        align = int(g)
    bursts = n_runs * _mean_bursts(run, align, burst, dt)
    rows = n_ext * np.maximum(one, span / dt(row_words))
    return bursts, rows


# -- per-node cost model ----------------------------------------------------------


@dataclass(frozen=True)
class NodeCost:
    latency_s: float
    energy_pj: float
    e_mac_pj: float
    e_sram_pj: float
    e_dram_pj: float
    tiling: tuple
    bpq_outer: bool


def tile_candidates(dim: int, cap: int = 7) -> list[int]:
    out, t = [], 1
    while t < dim:
        out.append(t)
        t *= 2
    out.append(dim)
    return out[-cap:] if len(out) > cap else out


def _sram_pj_per_bit(kib: int, dt):
    return dt(SRAM_BASE_PJ_PER_BIT) + dt(SRAM_LOG_PJ_PER_BIT) * dt(
        math.log2(max(2, kib)))


@lru_cache(maxsize=1 << 17)
def node_cost(hw: Hw, layer: Layer, dl_in: DL, dl_out: DL,
              dt=np.float64) -> NodeCost:
    """Latency/energy of one part-layer resident on one PIM node."""
    if not layer.heavy:
        return NodeCost(0.0, 0.0, 0.0, 0.0, 0.0, (1, 1, 1, 1, 1), False)
    c = dict(hw.cons)
    B, C, H, W = layer.B, layer.C, layer.H, layer.W
    K, HK, WK, s = layer.K, layer.HK, layer.WK, layer.stride
    P, Q = layer.P, layer.Q
    dbytes, pbytes = c["data_bits"] // 8, c["psum_bits"] // 8
    burst = max(1, hw.node_dram_width_bits // c["data_bits"])
    row_words = max(burst, c["dram_row_bytes"] * hw.banks_per_node // dbytes)
    tqs = [Q] if Q <= 64 else tile_candidates(Q, cap=4)
    TB, TK, TC, TP, TQ = (a.reshape(-1) for a in np.meshgrid(
        np.array(tile_candidates(B, cap=4), np.int64),
        np.array(tile_candidates(K), np.int64),
        np.array(tile_candidates(C), np.int64),
        np.array(tile_candidates(P), np.int64),
        np.array(tqs, np.int64), indexing="ij"))
    TH = (TP - 1) * s + HK
    TW = (TQ - 1) * s + WK
    fits = ((TB * TC * TH * TW * dbytes * 2 <= hw.ibuf_kib * 1024)
            & (TK * TC * HK * WK * dbytes * 2 <= hw.wbuf_kib * 1024)
            & (TB * TK * TP * TQ * pbytes <= hw.obuf_kib * 1024))
    if not fits.any():
        fits = np.zeros_like(fits)
        fits[int(np.argmin(TB * TC * TH * TW))] = True
    TB, TK, TC, TP, TQ, TH, TW = (a[fits] for a in
                                  (TB, TK, TC, TP, TQ, TH, TW))
    f = [a.astype(dt) for a in (TB, TK, TC, TP, TQ, TH, TW)]
    tb, tk, tc, tp, tq, th, tw = f
    one = dt(1)
    n_k = np.ceil(dt(K) / tk)
    n_c = np.ceil(dt(C) / tc)
    n_bpq = np.ceil(dt(B) / tb) * np.ceil(dt(P) / tp) * np.ceil(dt(Q) / tq)
    n_ti = np.ceil(dt(B) / tb) * n_c * np.ceil(dt(P) / tp) * np.ceil(
        dt(Q) / tq)
    n_to = np.ceil(dt(B) / tb) * n_k * np.ceil(dt(P) / tp) * np.ceil(
        dt(Q) / tq)
    cyc = (np.ceil(tc / dt(hw.pea_row)) * np.ceil(tk / dt(hw.pea_col))
           * dt(HK) * dt(WK) * tp * tq * tb)
    compute = cyc * n_k * n_c * n_bpq
    ib, ir = _tile_fetch((B, C, H, W), tb, tc, th, tw, dl_in, burst,
                         row_words, dt)
    ob, orow = _tile_fetch((B, K, P, Q), tb, tk, tp, tq, dl_out, burst,
                           row_words, dt)
    w_vals = dt(layer.weight_count)
    w_bursts = np.ceil(w_vals / dt(burst))
    w_rows = np.maximum(one, w_vals / dt(row_words))
    all_w = K * C * HK * WK * dbytes * 2 <= hw.wbuf_kib * 1024
    all_i = B * C * H * W * dbytes * 2 <= hw.ibuf_kib * 1024
    i_ko = np.where(all_i, one, n_k)
    w_bo = np.where(all_w, one, n_bpq)
    i_vals, o_vals = dt(B * C * H * W), dt(B * K * P * Q)

    def dram(i_p, w_p):
        return (ib * n_ti * i_p + w_bursts * w_p + ob * n_to,
                ir * n_ti * i_p + w_rows * w_p + orow * n_to,
                i_vals * i_p + w_vals * w_p + o_vals)

    miss = dt(c["dram_row_miss_cycles"])
    b_ko, r_ko, v_ko = dram(i_ko, one)
    b_bo, r_bo, v_bo = dram(one, w_bo)
    d_ko, d_bo = b_ko + r_ko * miss, b_bo + r_bo * miss
    use_bo = d_bo < d_ko
    dram_cyc = np.where(use_bo, d_bo, d_ko)
    bursts = np.where(use_bo, b_bo, b_ko)
    rows = np.where(use_bo, r_bo, r_ko)
    values = np.where(use_bo, v_bo, v_ko)
    total = np.maximum(compute, dram_cyc)
    i = int(np.argmin(total))
    macs = dt(layer.macs)
    e_mac = macs * dt(MAC_ENERGY_PJ)
    ibuf_r = macs / dt(max(1, min(int(TK[i]), hw.pea_col)))
    wbuf_r = macs / dt(max(1, int(TB[i]) * int(TP[i]) * int(TQ[i])))
    obuf_a = dt(2) * macs / dt(max(1, min(int(TC[i]), hw.pea_row)))
    e_sram = (ibuf_r * dt(c["data_bits"]) * _sram_pj_per_bit(hw.ibuf_kib, dt)
              + wbuf_r * dt(c["data_bits"]) * _sram_pj_per_bit(hw.wbuf_kib,
                                                               dt)
              + obuf_a * dt(c["psum_bits"]) * _sram_pj_per_bit(hw.obuf_kib,
                                                               dt))
    moved = bursts[i] * dt(hw.node_dram_width_bits)
    useful = values[i] * dt(c["data_bits"])
    e_dram = (max(moved, useful) * dt(c["dram_energy_pj_per_bit"])
              + rows[i] * dt(c["dram_row_act_energy_pj"]))
    return NodeCost(
        latency_s=float(total[i] / dt(c["freq_hz"])),
        energy_pj=float(e_mac + e_sram + e_dram),
        e_mac_pj=float(e_mac), e_sram_pj=float(e_sram),
        e_dram_pj=float(e_dram),
        tiling=(int(TB[i]), int(TK[i]), int(TC[i]), int(TP[i]), int(TQ[i])),
        bpq_outer=bool(use_bo[i]))

