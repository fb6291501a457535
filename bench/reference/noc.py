"""Mesh NoC, sharing problems and the link loads of a schedule (Sec. VII).

A layer's data sharing is up to three problems on its region's mesh:
weights among the replica subsets of each (K, C) partition, inputs among
the K partitions, partial sums among the C partitions.  A schedule gives
one Hamilton cycle per sharing set; each cycle edge ``a -> b`` carries
``(N - 1) * chunk`` bytes along the XY route, and the process takes the
hottest link's bytes over the link bandwidth plus the longest route's
router delay (Eq. 4).  The reference checks a schedule instead of
searching for one: it recomputes the loads, and bounds the optimum by the
brute force (small single sets) or by the search's own starting cycles.
``local_search`` runs a plain copy of the search, for a reading of the
search's quality that ``correct`` does not judge.
"""

from __future__ import annotations

import itertools
import math
import random
from functools import lru_cache

import numpy as np

from .mapper import LOOPS, LM, loop_strides


class Mesh:
    def __init__(self, rows: int, cols: int):
        self.rows, self.cols = rows, cols
        idx = {}
        for r in range(rows):
            for c in range(cols):
                n = r * cols + c
                if c + 1 < cols:
                    idx[(n, n + 1)] = len(idx)
                    idx[(n + 1, n)] = len(idx)
                if r + 1 < rows:
                    idx[(n, n + cols)] = len(idx)
                    idx[(n + cols, n)] = len(idx)
        self.link = idx
        self.n_links = len(idx)

    def route(self, a: int, b: int) -> list[int]:
        (r, c), (dr, dc) = divmod(a, self.cols), divmod(b, self.cols)
        out = []
        while c != dc:
            step = 1 if dc > c else -1
            out.append(self.link[(r * self.cols + c,
                                  r * self.cols + c + step)])
            c += step
        while r != dr:
            step = 1 if dr > r else -1
            out.append(self.link[(r * self.cols + c,
                                  (r + step) * self.cols + c)])
            r += step
        return out

    def hops(self, a: int, b: int) -> int:
        (r, c), (dr, dc) = divmod(a, self.cols), divmod(b, self.cols)
        return abs(r - dr) + abs(c - dc)


@lru_cache(maxsize=256)
def mesh(rows: int, cols: int) -> Mesh:
    return Mesh(rows, cols)


def transfers(cycles, chunks):
    out = []
    for cyc, ch in zip(cycles, chunks):
        n = len(cyc)
        if n > 1:
            out.extend((cyc[i], cyc[(i + 1) % n], (n - 1) * ch)
                       for i in range(n))
    return out


def max_load(m: Mesh, tr, dt=np.float64) -> float:
    loads = np.zeros(m.n_links, dtype=dt)
    for a, b, nbytes in tr:
        if a != b and nbytes > 0:
            for e in m.route(a, b):
                loads[e] += dt(nbytes)
    return float(loads.max()) if loads.size else 0.0


def cost(m: Mesh, cycles, chunks, link_bw: float, freq: float,
         pj_per_bit_hop: float, dt=np.float64) -> tuple[float, float, float]:
    """(max link bytes, latency s, energy pJ) of one schedule."""
    tr = transfers(cycles, chunks)
    if not tr:
        return 0.0, 0.0, 0.0
    mx = max_load(m, tr, dt)
    hops = [m.hops(a, b) for a, b, nb in tr if nb > 0]
    lat = dt(mx) / dt(link_bw) + dt(max(hops, default=0) * 2) / dt(freq)
    en = dt(sum(dt(nb) * dt(8) * dt(m.hops(a, b)) for a, b, nb in tr)) \
        * dt(pj_per_bit_hop)
    return mx, float(lat), float(en)


# -- the sharing problems of one mapped layer ---------------------------------


def _indices(lm: LM, loops):
    outs = [dict()]
    for l in loops:
        i = LOOPS.index(l)
        outs = [{**d, l: (a, b)} for a in range(lm.ph[i])
                for b in range(lm.pw[i]) for d in outs]
    return outs


def _node(lm: LM, cols: int, idx: dict) -> int:
    st = loop_strides(lm)
    h = w = 0
    for l in LOOPS:
        ih, iw = idx.get(l, (0, 0))
        h += ih * st[l][0]
        w += iw * st[l][1]
    return h * cols + w


def sharing_problems(lm: LM, shape: tuple, wr: int, w_bytes: int,
                     i_bytes: int, p_bytes: int) -> list:
    """``[(sets, chunk)]``: the layer's sharing processes on its region."""
    cols = shape[1]
    out = []

    def add(sets, chunk):
        kept = tuple(tuple(s) for s in sets if len(s) > 1)
        if kept and chunk > 0:
            out.append((kept, chunk))

    n_ws = lm.weight_share
    group = math.ceil(n_ws / max(1, min(wr, n_ws)))
    if group > 1 and w_bytes > 0:
        share = tuple(l for l in ("B", "P", "Q") if lm.parts(l) > 1)
        sets = []
        for idx in _indices(lm, tuple(l for l in ("K", "C")
                                      if lm.parts(l) > 1)):
            nodes = [_node(lm, cols, {**idx, **sub})
                     for sub in _indices(lm, share)]
            sets.extend(nodes[s:s + group]
                        for s in range(0, len(nodes), group))
        add(sets, w_bytes / group)
    if lm.parts("K") > 1 and i_bytes > 0:
        other = tuple(l for l in ("B", "P", "Q", "C") if lm.parts(l) > 1)
        add([[_node(lm, cols, {**idx, **sub}) for sub in _indices(lm, ("K",))]
             for idx in _indices(lm, other)], i_bytes / lm.parts("K"))
    if lm.parts("C") > 1 and p_bytes > 0:
        other = tuple(l for l in ("B", "P", "Q", "K") if lm.parts(l) > 1)
        add([[_node(lm, cols, {**idx, **sub}) for sub in _indices(lm, ("C",))]
             for idx in _indices(lm, other)], 2 * p_bytes / lm.parts("C"))
    return out


# -- bounds on the optimum -------------------------------------------------------


def exhaustive(m: Mesh, sets, chunk) -> tuple[float, list]:
    """Least max-link load of one small set and its first optimal cycle."""
    s = sets[0]
    best, best_cyc = math.inf, None
    for p in itertools.permutations(s[1:]):
        load = max_load(m, transfers([[s[0], *p]], [chunk]))
        if load < best:
            best, best_cyc = load, [[s[0], *p]]
    return best, best_cyc


def _snake(m: Mesh, n: int, flip: bool):
    r, c = divmod(n, m.cols)
    if flip:
        return (c, r if c % 2 == 0 else m.rows - 1 - r)
    return (r, c if r % 2 == 0 else m.cols - 1 - c)


def _tsp(m: Mesh, nodes):
    rem, cyc = list(nodes[1:]), [nodes[0]]
    while rem:
        nxt = min(rem, key=lambda n: m.hops(cyc[-1], n))
        rem.remove(nxt)
        cyc.append(nxt)
    n, improved = len(cyc), True
    while improved:
        improved = False
        for i in range(1, n - 1):
            for j in range(i + 1, n):
                a, b, c, d = cyc[i - 1], cyc[i], cyc[j], cyc[(j + 1) % n]
                if (m.hops(a, c) + m.hops(b, d)
                        < m.hops(a, b) + m.hops(c, d)):
                    cyc[i:j + 1] = cyc[i:j + 1][::-1]
                    improved = True
    return cyc


def starts(m: Mesh, sets) -> list:
    """The search's deterministic starting schedules: alternating
    row/column snakes, per-set shortest tours, row snakes."""
    return [[sorted(s, key=lambda n: _snake(m, n, si % 2 == 1))
             for si, s in enumerate(sets)],
            [_tsp(m, list(s)) for s in sets],
            [sorted(s, key=lambda n: _snake(m, n, False)) for s in sets]]


def start_schedule(m: Mesh, sets, chunk) -> list:
    """The best of the search's deterministic starting schedules.

    The search never accepts a worse schedule, so its result may not exceed
    the best of these.
    """
    return min(starts(m, sets), key=lambda c: max_load(
        m, transfers(c, [chunk] * len(sets))))


def _loads(m: Mesh, cycles, chunk) -> np.ndarray:
    loads = np.zeros(m.n_links)
    for a, b, nbytes in transfers(cycles, [chunk] * len(cycles)):
        if a != b:
            loads[m.route(a, b)] += nbytes
    return loads


def _reversal(m: Mesh, cyc, i: int, j: int, weight: float) -> np.ndarray:
    """Link-load change of reversing ``cyc[i:j+1]`` (routes are directed)."""
    n = len(cyc)
    prv, nxt = cyc[(i - 1) % n], cyc[(j + 1) % n]
    gone = [(prv, cyc[i]), (cyc[j], nxt)] + [(cyc[k], cyc[k + 1])
                                              for k in range(i, j)]
    new = [(prv, cyc[j]), (cyc[i], nxt)] + [(cyc[k + 1], cyc[k])
                                             for k in range(i, j)]
    d = np.zeros(m.n_links)
    for sign, edges in ((1.0, new), (-1.0, gone)):
        for a, b in edges:
            if a != b:
                np.add.at(d, m.route(a, b), sign)
    return d * weight


def local_search(m: Mesh, sets, chunk, seed: int = 0, restarts: int = 4,
                 iters: int = 400, moves_per_round: int = 32) -> float:
    """Hottest-link bytes of a plain multi-start 2-opt search (Sec. VII).

    The same budget and rules as the program's search: from each start
    (the deterministic ones, then shuffles), rounds of random segment
    reversals, the best non-worsening move of each set applied, until the
    budget or a stall; the best restart wins.
    """
    rng = random.Random(seed)
    weights = [(len(s) - 1) * chunk for s in sets]
    rounds = max(1, -(-iters // moves_per_round))
    stall_limit = max(2, 60 // moves_per_round)
    first = starts(m, sets)
    best = math.inf
    for r in range(max(3, restarts)):
        cycles = ([list(c) for c in first[r]] if r < len(first)
                  else [rng.sample(list(s), len(s)) for s in sets])
        loads = _loads(m, cycles, chunk)
        obj, stall = loads.max(), 0
        for _ in range(rounds):
            open_ = [si for si, c in enumerate(cycles) if len(c) >= 4]
            if stall > stall_limit or not open_:
                break
            moves = []
            for _ in range(moves_per_round):
                si = open_[rng.randrange(len(open_))]
                n = len(cycles[si])
                i, j = sorted(rng.sample(range(n), 2))
                while (i, j) == (0, n - 1):
                    i, j = sorted(rng.sample(range(n), 2))
                moves.append((si, i, j))
            deltas = [_reversal(m, cycles[si], i, j, weights[si])
                      for si, i, j in moves]
            objs = [(loads + d).max() for d in deltas]
            improved, touched = False, set()
            for k in np.argsort(objs, kind="stable"):
                si, i, j = moves[k]
                if si in touched:
                    continue
                cand = loads + deltas[k]
                if cand.max() <= obj:
                    improved |= cand.max() < obj
                    touched.add(si)
                    c = cycles[si]
                    cycles[si] = c[:i] + c[i:j + 1][::-1] + c[j + 1:]
                    loads, obj = cand, cand.max()
            stall = 0 if improved else stall + 1
        best = min(best, max_load(m, transfers(cycles,
                                               [chunk] * len(cycles))))
    return best
