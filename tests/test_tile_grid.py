"""The candidate tiling grid derived inside ``_batch_cost``, and its programs.

``_batch_cost`` derives each part-layer's tiling grid from the layer's
dims on the device; the host ships one row of fields per part-layer.

* the derived grid equals the scalar model's ``np.meshgrid`` grid exactly:
  values, order, size, input window and the no-fit fallback index, on every
  part-layer a GoogLeNet ``map_many`` costs and on edge dims;
* paired dispatches compile one program per ``(pair block, T-bucket)``,
  the classes the warm-up reaches, and nothing more once those are warm.
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.costmodel import _tile_candidates
from repro.core.hardware import PAPER_4X4, PAPER_BEST
from repro.core.ir import Layer
from repro.core.layout import DataLayout
from repro.core.mapper import PimMapper, clear_mapper_caches
from repro.core.workloads import googlenet
from repro.engine import batch_cost, overlap
from repro.engine.batch_cost import (PartSpec, _batch_cost, _grid_size,
                                     _prep_specs, _t_bucket, _tile_grid)
from repro.engine.overlap import dispatch_paired_latency
from repro.runtime import x64

DL = DataLayout("BCHW", 1)


def _oracle_grid(layer: Layer) -> np.ndarray:
    """``part_layer_cost``'s candidate grid, as the scalar model builds it."""
    tks = np.array(_tile_candidates(layer.K), dtype=np.int64)
    tcs = np.array(_tile_candidates(layer.C), dtype=np.int64)
    tps = np.array(_tile_candidates(layer.P), dtype=np.int64)
    tqs = np.array([layer.Q], dtype=np.int64) if layer.Q <= 64 else \
        np.array(_tile_candidates(layer.Q, cap=4), dtype=np.int64)
    tbs = np.array(_tile_candidates(layer.B, cap=4), dtype=np.int64)
    return np.stack([a.reshape(-1) for a in
                     np.meshgrid(tbs, tks, tcs, tps, tqs, indexing="ij")])


def _conv(B, K, C, P, Q, k=1, stride=1):
    """A conv whose output is ``P x Q`` (unpadded, ``k x k`` kernel)."""
    return Layer("x", "conv", B=B, C=C, H=(P - 1) * stride + k,
                 W=(Q - 1) * stride + k, K=K, HK=k, WK=k, stride=stride)


def _each_axis(values, other=5):
    """Every value on every axis (the rest at ``other``), and on all five."""
    out = []
    for v in values:
        out.append(_conv(v, v, v, v, v))
        for axis in range(5):
            dims = [other] * 5
            dims[axis] = v
            out.append(_conv(*dims))
    return out


def _map_many_layers():
    """Every distinct part-layer a GoogLeNet-224 ``map_many`` costs on two
    Table-II points, recorded where the host packs them."""
    seen = {}
    real = batch_cost._prep_specs

    def recording(specs, **kw):
        for s in specs:
            seen.setdefault(s.layer, None)
        return real(specs, **kw)
    mp = pytest.MonkeyPatch()
    try:
        mp.setattr(batch_cost, "_prep_specs", recording)
        mp.setattr(overlap, "_prep_specs", recording)
        clear_mapper_caches()
        PimMapper(PAPER_BEST, max_optim_iter=1, lm_cap=60, n_wr=3).map_many(
            googlenet(1), [PAPER_BEST, PAPER_4X4])
    finally:
        mp.undo()
        clear_mapper_caches()
    return list(seen)


GRID_CASES = {
    "googlenet_map_many": _map_many_layers,
    "dims_1_2_3": lambda: [_conv(*d) for d in
                           itertools.product((1, 2, 3), repeat=5)],
    "powers_of_two": lambda: _each_axis([1 << i for i in range(13)]),
    "powers_of_two_pm1": lambda: _each_axis(
        [(1 << i) + e for i in range(2, 13) for e in (-1, 1)]),
    "q_64_65": lambda: _each_axis([63, 64, 65, 127, 128, 129], other=9),
    "b_over_8": lambda: [_conv(b, 64, 32, 28, q) for b in
                         (9, 16, 17, 33, 64, 100) for q in (7, 65, 112)],
    "over_the_cap": lambda: _each_axis([65, 100, 255, 1000, 2048, 5000],
                                       other=300),
    "kernels_and_strides": lambda: [
        _conv(b, 96, c, p, q, k=k, stride=s)
        for b, c, p, q in ((1, 3, 112, 112), (2, 64, 28, 65), (8, 1, 7, 1))
        for k, s in ((1, 1), (3, 1), (3, 2), (5, 1), (7, 2))],
}


@pytest.mark.parametrize("case", list(GRID_CASES))
def test_device_grid_equals_the_scalar_models_grid(case):
    layers = GRID_CASES[case]()
    assert layers
    lay_np, t = _prep_specs([PartSpec(l, DL, DL) for l in layers])
    with x64():
        g = jax.jit(_tile_grid, static_argnums=1)(
            {k: jnp.asarray(v) for k, v in lay_np.items()}, t)
        g = {k: np.asarray(v) for k, v in g.items()}
    assert g["tb"].dtype == np.int64 and g["tb"].shape == (len(layers), t)
    for row, layer in enumerate(layers):
        want = _oracle_grid(layer)
        size = want.shape[1]
        assert _grid_size(layer) == size, layer
        assert g["valid"][row].sum() == size and g["valid"][row, :size].all()
        got = np.stack([g[k][row, :size] for k in ("tb", "tk", "tc", "tp",
                                                   "tq")])
        np.testing.assert_array_equal(got, want, err_msg=str(layer))
        tb, _, tc, tp, tq = want
        th = (tp - 1) * layer.stride + layer.HK
        tw = (tq - 1) * layer.stride + layer.WK
        np.testing.assert_array_equal(g["th"][row, :size], th)
        np.testing.assert_array_equal(g["tw"][row, :size], tw)
        assert g["fallback"][row, 0] == np.argmin(tb * tc * th * tw), layer


def _layer_in_bucket(bucket: int) -> Layer:
    """A 1x1 conv whose tiling grid falls in ``bucket``."""
    dims = [1 << i for i in range(8)]
    for K, C, P, Q in itertools.product(dims, dims, dims, (1, 128)):
        layer = _conv(1, K, C, P, Q)
        if _t_bucket(layer) == bucket:
            return layer
    raise ValueError(bucket)


#: the pair counts ``bench/warmup.py`` dispatches in every T-bucket
WARM_COUNTS = (100, 200, 400, 1000)


@pytest.mark.parametrize("bucket", [128, 256, 512, 1024])
def test_paired_dispatch_compiles_one_program_per_class(monkeypatch, bucket):
    seen = []
    real = overlap._batch_cost

    def recording(cfg, lay, **kw):
        seen.append((lay["B"].shape[0], kw["t_pad"]))
        return real(cfg, lay, **kw)
    monkeypatch.setattr(overlap, "_batch_cost", recording)
    spec = PartSpec(_layer_in_bucket(bucket), DL, DL)
    for count in WARM_COUNTS:
        dispatch_paired_latency([PAPER_BEST] * count,
                                [spec] * count).latency_row()
    classes = {(n_pad, bucket) for n_pad in (128, 256, 512, 1024)}
    assert seen == sorted(classes)
    warm = _batch_cost._cache_size()
    seen.clear()
    for count in (1, 127, 129, 255, 700, 1024):
        dispatch_paired_latency([PAPER_4X4] * count,
                                [spec] * count).latency_row()
    assert set(seen) <= classes
    assert _batch_cost._cache_size() == warm
