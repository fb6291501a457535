"""Ahead-of-time compiles of the main-path kernels and jits for a TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached: these tests catch what interpret mode cannot — a
block shape off the (8, 128) tiling, an operand layout Mosaic and XLA
disagree on, a dtype Mosaic refuses, scoped-VMEM overflow — at the sizes
the DSE campaign really runs, without a chip.  Nothing runs, so nothing
here says anything about results or speed.

The topology is described inside a module fixture (never at import: only
one process at a time may load the TPU library), and the tests skip where
it cannot be described.  ``native`` steers :func:`repro.runtime.backend` to
``"tpu"`` so the kernels take their native path, and turns the persistent
compile cache off around the compiles.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro import runtime


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def native(monkeypatch, one_chip):
    """Native kernels, no persistent cache, no traces shared with CPU tests.

    Kernel wrappers resolve ``interpret`` while their caller is traced, so
    a jaxpr cached by a CPU test would hide the native kernel (and one
    traced here must not leak into a CPU test): clear the trace caches on
    both sides.
    """
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setattr(runtime, "backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.clear_caches()
    yield one_chip
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(sharding, tree):
    """Shape/dtype/sharding stand-ins for a pytree of host arrays."""
    return jax.tree.map(
        lambda v: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype,
                                       sharding=sharding), tree)


def _compile(fn, *args, **kw) -> str:
    return fn.lower(*args, **kw).compile().as_text()


# ---------------------------------------------------------------------------
# the two f32 Pallas kernels
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("q,n", [(2048, 8), (2048, 128), (512, 64)])
def test_lcb_rows_compiles(native, q, n):
    """The tuner's propose kernel: 2048 candidates x pow2 training buckets."""
    from repro.kernels import dse_eval
    f32 = np.float32
    fn = jax.jit(lambda zq, zt, a, k, v: dse_eval.lcb_rows(
        zq, zt, a, k, v, 1.0, 1.0, 1.0))
    txt = _compile(fn, *_sds(native, (
        np.zeros((q, 16), f32), np.zeros((n, 16), f32), np.zeros(n, f32),
        np.zeros((n, n), f32), np.zeros(n, bool))))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("r,m,e", [(32, 32, 64), (32, 32, 960),
                                   (32, 32, 1024), (4, 130, 60)])
def test_delta_maxload_rows_compiles(native, r, m, e):
    """The scheduler's move scoring, up to the 960-link 16x16 mesh."""
    from repro.kernels import dse_eval
    fn = jax.jit(dse_eval.delta_maxload_rows)
    txt = _compile(fn, *_sds(native, (
        np.zeros((r, e), np.float32), np.zeros((r, m, e), np.int16),
        np.zeros((r, m), np.float32))))
    assert "tpu_custom_call" in txt


# ---------------------------------------------------------------------------
# the engine jits
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("paired", [False, True], ids=["grid", "paired"])
def test_batch_cost_compiles(native, paired):
    """``_batch_cost`` at ``spec_chunk=1024`` on a 128-wide T-bucket, f64.

    Grid mode is the mapper's one-config sweep, paired mode the
    multi-config sweep.  Its reduction is plain f64 XLA: no kernel.
    """
    from repro.core.hardware import PAPER_BEST
    from repro.core.layout import DataLayout
    from repro.core.workloads import googlenet
    from repro.engine.batch_cost import (PartSpec, _batch_cost, _grid_size,
                                         _prep_configs, _prep_specs)
    specs = [PartSpec(l, DataLayout("BCHW", 8), DataLayout("BHWC"))
             for l in googlenet(1).layers if _grid_size(l) <= 128]
    specs = (specs * (1024 // len(specs) + 1))[:1024]
    lay, t = _prep_specs(specs, t_pad=128)
    cfg, cons = _prep_configs([PAPER_BEST] * (1024 if paired else 1))
    with runtime.x64():
        txt = _compile(_batch_cost, _sds(native, cfg), _sds(native, lay),
                       t_pad=t, data_bits=cons.data_bits,
                       psum_bits=cons.psum_bits,
                       dram_row_miss=cons.dram_row_miss_cycles,
                       paired=paired)
    assert "tpu_custom_call" not in txt


def test_fused_propose_compiles(native):
    """The device-resident propose chain of ``engine/pipeline.py``."""
    from repro.core.tuner import DKL_SIZES, FILTER_SIZES
    from repro.engine.pipeline import _area_mask, _select_topk
    from repro.engine.tuner_train import _score_candidates_jit, mlp_init
    key = jax.random.PRNGKey(0)
    params = {"mlp": mlp_init(key, DKL_SIZES), "log_ls": jnp.zeros(()),
              "log_sf": jnp.zeros(()), "log_sn": jnp.zeros(())}
    q, n = 2048, 64
    f32 = np.float32
    ok = np.ones(q, bool)
    txt = _compile(_score_candidates_jit, *_sds(native, (
        params, np.zeros((n, 7), f32), np.zeros(n, f32), np.ones(n, bool),
        np.zeros((q, 7), f32), ok, f32(1.0))),
        use_pallas=runtime.native_kernels())
    assert "tpu_custom_call" in txt
    _compile(_area_mask, *_sds(native, (mlp_init(key, FILTER_SIZES),
                                        np.zeros((q, 7), f32), f32(48.0))))
    _compile(_select_topk, *_sds(native, (np.zeros((q, 7), np.int32),
                                          np.zeros(q, f32), ok)), k=8)


def test_sharded_propose_compiles_on_four_chips(native, topo):
    """The sharded campaign's propose chain on a 4-chip ``config`` mesh.

    The native ``lcb_rows`` kernel must run per device (XLA refuses to
    partition a Pallas call); the jnp stages are partitioned by GSPMD.
    """
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core.tuner import DKL_SIZES, FILTER_SIZES
    from repro.engine.pipeline import _area_mask, _select_topk
    from repro.engine.sharded import _scores_for, _wave_stats_for
    from repro.engine.tuner_train import mlp_init
    mesh = Mesh(np.array(topo.devices), ("config",))
    rows, rep = NamedSharding(mesh, P("config")), NamedSharding(mesh, P())
    key = jax.random.PRNGKey(0)
    params = {"mlp": mlp_init(key, DKL_SIZES), "log_ls": jnp.zeros(()),
              "log_sf": jnp.zeros(()), "log_sn": jnp.zeros(())}
    q, n = 512, 64
    f32 = np.float32
    xq, ok = _sds(rows, (np.zeros((q, 7), f32), np.ones(q, bool)))
    txt = _compile(_scores_for(mesh, runtime.native_kernels()),
                   *_sds(rep, (params, np.zeros((n, 7), f32),
                               np.zeros(n, f32), np.ones(n, bool))),
                   xq, ok, _sds(rep, f32(1.0)))
    assert "tpu_custom_call" in txt
    scores = _sds(rows, np.zeros(q, f32))
    _compile(_area_mask, _sds(rep, mlp_init(key, FILTER_SIZES)), xq,
             _sds(rep, f32(48.0)))
    _compile(_select_topk, _sds(rows, np.zeros((q, 7), np.int32)), scores,
             ok, k=8)
    _compile(_wave_stats_for(mesh), scores, ok)


def test_tuner_fits_compile(native):
    """Whole-trajectory Adam scans of the filter and DKL models."""
    from repro.core.tuner import (_DKL_OPT, _FILTER_OPT, DKL_SIZES,
                                  FILTER_SIZES)
    from repro.engine.tuner_train import (_fit_dkl_jit, _fit_filter_jit,
                                          mlp_init)
    key = jax.random.PRNGKey(0)
    n = 64
    data = (np.zeros((n, 7), np.float32), np.zeros(n, np.float32),
            np.ones(n, bool))
    fparams = mlp_init(key, FILTER_SIZES)
    _compile(_fit_filter_jit, *_sds(native, (
        fparams, _FILTER_OPT.init(fparams)) + data),
        opt=_FILTER_OPT, steps=8)
    dparams = {"mlp": mlp_init(key, DKL_SIZES), "log_ls": jnp.zeros(()),
               "log_sf": jnp.zeros(()), "log_sn": jnp.zeros(())}
    _compile(_fit_dkl_jit, *_sds(native, (
        dparams, _DKL_OPT.init(dparams)) + data), opt=_DKL_OPT, steps=8)


def test_scan_solve_compiles(native):
    """The scheduler's 2-opt scan on the 16x16 mesh (960 links -> 1024)."""
    from repro.engine.scheduler_opt import _R_CHUNK, _scan_solve
    r, s, n, nn, e = _R_CHUNK, 4, 64, 256, 1024
    with runtime.x64():
        txt = _compile(_scan_solve, *_sds(native, (
            np.zeros((r, s, n), np.int32), np.zeros((r, s), np.int32),
            np.zeros((r, s)), np.zeros((r, e)), np.zeros((r, 2), np.uint32),
            np.zeros((nn, nn, e), np.int8))),
            rounds=13, n_moves=32, use_pallas=runtime.native_kernels())
    assert "tpu_custom_call" in txt


def test_fold_keys_compiles(native):
    """The scheduler's per-bucket PRNG key folding."""
    from repro.engine.scheduler_opt import _fold_keys
    u32 = np.zeros(32, np.uint32)
    _compile(_fold_keys, *_sds(native, (u32, u32, u32)))
