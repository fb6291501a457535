"""Warm every device program the window can reach, from shapes.

Three families of programs depend on what a campaign meets, not on the
configuration alone, and a fresh seed meets new ones:

* device costing: one program per (candidate-grid bucket, pair bucket);
  the grid buckets reachable are bounded by the largest unpartitioned
  layer of the graph, the pair buckets by the chunk size;
* the Data-Scheduler search: one program per (region mesh, set count,
  set size) class; the region meshes are those the slicing trees cut from
  every node array of the design space, the sets any split of a region;
* the tuner: one fit and one scoring program per observation bucket, up to
  the traffic's ``max_observations``.

Each family is driven through the program's public entry points with
synthetic inputs of every reachable class, which compiles (cold) or loads
(warm) the same programs the campaign would.  The envelope is an
over-approximation: it warms some classes no campaign of the seed reaches.
"""

from __future__ import annotations

import itertools

import numpy as np

from reference.mapper import sm_candidates
from reference.model import Graph, tile_candidates


def _pow2(n: int, minimum: int) -> int:
    return max(minimum, 1 << max(0, (int(n) - 1).bit_length()))


def _pow4(n: int, minimum: int) -> int:
    p = _pow2(n, minimum)
    return p * 2 if (p.bit_length() - 1) % 2 else p


def grid_size(K: int, C: int, P: int, Q: int, B: int = 1) -> int:
    q = 1 if Q <= 64 else len(tile_candidates(Q, cap=4))
    return (len(tile_candidates(B, cap=4)) * len(tile_candidates(K))
            * len(tile_candidates(C)) * len(tile_candidates(P)) * q)


def costing_classes(config: dict) -> list[int]:
    """Candidate-grid buckets (floor 128) a part-layer of the graph can hit."""
    top = 128
    for row in config["graph"]["layers"]:
        if row["kind"] not in ("conv", "matmul", "dwconv"):
            continue
        P = max(1, (row["H"] + 2 * row["pad"] - row["HK"]) // row["stride"]
                + 1)
        Q = max(1, (row["W"] + 2 * row["pad"] - row["WK"]) // row["stride"]
                + 1)
        top = max(top, _pow2(grid_size(row["K"], row["C"], P, Q, row["B"]),
                             128))
    return [1 << b for b in range(7, top.bit_length())]


def _layer_for_bucket(bucket: int):
    """A 1x1 conv whose tiling grid falls in ``bucket``."""
    dims = [1 << i for i in range(0, 8)]
    for K, C, P, Q in itertools.product(dims, dims, dims, (1, 128)):
        if _pow2(grid_size(K, C, P, Q), 128) == bucket:
            return K, C, P, Q
    raise ValueError(f"no synthetic layer for grid bucket {bucket}")


def warm_costing(config: dict, hw, pair_counts=(100, 200, 400, 1000)):
    from repro.core.ir import Layer
    from repro.core.layout import DataLayout
    from repro.engine import PartSpec, dispatch_paired_latency
    dl = DataLayout("BCHW", 1)
    n = 0
    for bucket in costing_classes(config):
        K, C, P, Q = _layer_for_bucket(bucket)
        layer = Layer("warm", "conv", B=1, C=C, H=P, W=Q, K=K)
        for count in pair_counts:
            specs = [PartSpec(layer, dl, dl)] * count
            dispatch_paired_latency([hw] * count, specs).latency_row()
            n += 1
    return n


def region_shapes(config: dict) -> set:
    g = Graph.from_config(config["graph"])
    segs = g.segments()
    space = config["design_space"]
    shapes = set()
    for na_row in space["na_row"]:
        for na_col in space["na_col"]:
            for seg in segs:
                for sm in sm_candidates(g, seg.branches, na_row, na_col):
                    shapes.update((r.h_shape, r.w_shape) for r in sm.regions)
    return shapes


def scheduler_problems(shapes) -> list[tuple]:
    """Synthetic ``(rows, cols, sets)`` covering every set-count/size class.

    A region of ``n`` nodes splits into sharing sets of any size ``d``, about
    ``n / d`` of them; a problem reaches the search when some set has four
    or more nodes and it is not one set of seven or fewer (those are solved
    exhaustively on the host).  Problems are kept one per class of padded
    node and link counts and power-of-four set count and size, the shapes
    a search program is compiled for.
    """
    out = {}
    for h, w in sorted(shapes):
        n = h * w
        for d in range(4, n + 1):
            for c in {max(1, n // d), -(-n // d)}:
                if c == 1 and d <= 7:
                    continue
                links = 2 * (h * (w - 1) + w * (h - 1))
                key = (_pow2(n, 4), _pow2(links, 8), _pow4(c, 1),
                       _pow4(d, 4))
                if key not in out:
                    out[key] = (h, w, tuple(
                        tuple((i * d + j) % n for j in range(d))
                        for i in range(c)))
    return list(out.values())


def warm_scheduler(config: dict, hw) -> int:
    from repro.core.noc import MeshNoc
    from repro.engine.scheduler_opt import schedule_many
    scal = (hw.link_bw_bytes, hw.cons.freq_hz,
            hw.cons.noc_energy_pj_per_bit_hop)
    probs = [(MeshNoc(h, w), [list(s) for s in sets], [1024.0] * len(sets))
             for h, w, sets in scheduler_problems(region_shapes(config))]
    # two problems of a class share a bucket and its canonical row count;
    # one alone in its bucket may get rows of its own (large meshes do)
    schedule_many([p for p in probs for _ in range(2)], *scal)
    for p in probs:
        schedule_many([p], *scal)
    # every row count of the per-bucket key derivation
    for k in range(1, 9):
        schedule_many(probs[:1] * k, *scal)
    return len(probs)


def warm_tuner(n_sample: int, propose_k: int, max_observations: int,
               cons) -> int:
    from repro.core.hardware import HwConfig, sample_config_values
    from repro.core.tuner import PimTuner
    from repro.engine.pipeline import DsePipeline
    pipe = DsePipeline(PimTuner(cons=cons, seed=0, n_sample=n_sample,
                                backend="scan"))
    rng = np.random.default_rng(0)
    vals = sample_config_values(_pow2(max_observations, 8), rng, cons)
    have, n = 0, 0
    for bucket in (1 << b for b in range(3, _pow2(max_observations,
                                                  8).bit_length())):
        for row in vals[have:bucket]:
            cfg = HwConfig.from_tuple(row, cons=cons)
            pipe.observe(cfg, float(cfg.area_mm2()), 1.0 + float(row.sum()))
        have = bucket
        pipe.fit()
        pipe.propose_dispatch(propose_k).resolve()
        n += 1
    return n

