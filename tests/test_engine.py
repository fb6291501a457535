"""Batched DSE engine: parity, Pareto, cache, campaign, determinism."""

import json
import random

import numpy as np
import pytest

from repro.core.costmodel import part_layer_cost
from repro.core.hardware import (PAPER_4X4, PAPER_16X16, PAPER_BEST,
                                 DEFAULT_CONSTRAINTS, HwConfig)
from repro.core.ir import Layer, conv, matmul
from repro.core.layout import DataLayout
from repro.core.noc import MeshNoc
from repro.core.scheduler import solve_ilp_ls
from repro.core.tuner import sample_configs
from repro.core.workloads import googlenet
from repro.engine import (Campaign, EvalCache, ParetoFront, ParetoPoint,
                          PartSpec, batch_area_mm2, batch_max_link_load,
                          batch_part_cost, graph_digest, hw_digest)
from repro.engine.batch_cost import _t_bucket, batch_part_cost_paired

COST_FIELDS = ("latency_s", "energy_pj", "compute_s", "dram_s", "dram_bytes",
               "e_mac_pj", "e_sram_pj", "e_dram_pj")
# XLA's CPU backend contracts the SRAM energy's products and sums into fused
# multiply-adds, which the scalar NumPy model does not: these two differ
# from it by rounding alone, every other field is bitwise equal
ROUNDED_FIELDS = ("energy_pj", "e_sram_pj")


def _specs():
    layers = [
        conv("c1", 1, 64, 56, 56, 64),
        conv("c2", 4, 3, 224, 224, 32, stride=2),
        conv("c3", 1, 256, 14, 14, 512, HK=1),
        matmul("m1", 64, 768, 768),
        Layer("dw", "dwconv", B=1, C=128, H=28, W=28, K=128, HK=3, WK=3,
              stride=1, pad=1),
        Layer("aux", "add", B=1, C=64, H=56, W=56, K=64),
        conv("wideq", 1, 32, 112, 112, 64),   # exercises the Q > 64 path
    ]
    dls = [DataLayout("BCHW", 1), DataLayout("BCHW", 8), DataLayout("BHWC"),
           DataLayout("BCHW", 16)]
    return [PartSpec(l, dls[i % 4], dls[(i + 1) % 4])
            for i, l in enumerate(layers)]


# ---------------------------------------------------------------------------
# batch_cost vs scalar costmodel
# ---------------------------------------------------------------------------


def _googlenet_specs():
    """GoogLeNet-224's heavy layers (every T-bucket) and one aux layer."""
    net = googlenet(1)
    layers = [l for l in net.layers if l.is_heavy]
    layers.append(next(l for l in net.layers if not l.is_heavy))
    dls = [DataLayout("BCHW", 1), DataLayout("BCHW", 8), DataLayout("BHWC"),
           DataLayout("BCHW", 16)]
    return [PartSpec(l, dls[i % 4], dls[(i + 1) % 4])
            for i, l in enumerate(layers)]


@pytest.mark.parametrize("case", ["mixed", "googlenet_grid",
                                  "googlenet_paired"])
def test_batched_matches_scalar_on_randomized_configs(case):
    rng = np.random.default_rng(42)
    configs = [PAPER_BEST, PAPER_4X4, PAPER_16X16] + sample_configs(6, rng)
    paired = case == "googlenet_paired"
    if case == "mixed":
        specs = _specs()
        res = batch_part_cost(configs, specs, chunk=4)
    else:
        specs = _googlenet_specs()
        assert {_t_bucket(s.layer) for s in specs} >= {128, 256, 512, 1024}
        if paired:
            res = batch_part_cost_paired(
                [c for c in configs for _ in specs], specs * len(configs))
        else:
            res = batch_part_cost(configs, specs, chunk=4, spec_chunk=64)
    for i, cfg in enumerate(configs):
        for j, s in enumerate(specs):
            ref = part_layer_cost(cfg, s.layer, s.dl_in, s.dl_out)
            got = (res.part_cost(0, i * len(specs) + j) if paired
                   else res.part_cost(i, j))
            for f in COST_FIELDS:
                a, b = getattr(ref, f), getattr(got, f)
                if f in ROUNDED_FIELDS:
                    assert abs(a - b) <= 2 * np.spacing(abs(a)), \
                        (cfg.as_tuple(), s.layer.name, f)
                else:
                    assert a == b, (cfg.as_tuple(), s.layer.name, f)
            assert ref.tiling == got.tiling, (cfg.as_tuple(), s.layer.name)
            assert ref.loop_order == got.loop_order


def test_batched_aux_layer_is_zero():
    res = batch_part_cost([PAPER_4X4], _specs(), chunk=2)
    j = next(i for i, s in enumerate(res.specs) if not s.layer.is_heavy)
    assert res.latency_s[0, j] == 0.0
    assert res.energy_pj[0, j] == 0.0
    assert tuple(res.tiling[0, j]) == (1, 1, 1, 1, 1)


def test_batched_chunking_invariant():
    rng = np.random.default_rng(3)
    configs = sample_configs(5, rng)
    specs = _specs()[:3]
    a = batch_part_cost(configs, specs, chunk=2)
    b = batch_part_cost(configs, specs, chunk=5)
    np.testing.assert_allclose(a.latency_s, b.latency_s, rtol=0)
    np.testing.assert_allclose(a.energy_pj, b.energy_pj, rtol=0)


def test_batch_area_matches_scalar():
    rng = np.random.default_rng(7)
    configs = sample_configs(16, rng)
    areas = batch_area_mm2(configs)
    for c, a in zip(configs, areas):
        assert c.area_mm2() == pytest.approx(float(a), rel=1e-12)


def test_batch_max_link_load_matches_noc():
    noc = MeshNoc(4, 4)
    rng = random.Random(0)
    loads = []
    refs = []
    for _ in range(8):
        transfers = [(rng.randrange(16), rng.randrange(16),
                      float(rng.randrange(1, 100)))
                     for _ in range(12)]
        loads.append(noc.link_loads(transfers))
        refs.append(noc.max_link_load(transfers))
    got = batch_max_link_load(np.array(loads))
    np.testing.assert_allclose(got, refs, rtol=0)


# ---------------------------------------------------------------------------
# Pareto front
# ---------------------------------------------------------------------------


def _rand_points(rng, n=60):
    return [ParetoPoint(rng.uniform(1, 10), rng.uniform(1, 10),
                        rng.uniform(1, 10), payload=i) for i in range(n)]


def test_pareto_no_dominated_point_survives():
    rng = random.Random(1)
    pts = _rand_points(rng)
    fr = ParetoFront()
    fr.offer_all(pts)
    front = fr.front()
    for a in front:
        assert not any(b.dominates(a) for b in front)
    # everything excluded is dominated by (or duplicates) the front
    kept = {p.key for p in front}
    for p in pts:
        if p.key not in kept:
            assert fr.dominated(p) or p.key in kept


def test_pareto_insertion_order_invariance():
    rng = random.Random(2)
    pts = _rand_points(rng)
    keys = None
    for order_seed in range(4):
        shuffled = list(pts)
        random.Random(order_seed).shuffle(shuffled)
        fr = ParetoFront()
        fr.offer_all(shuffled)
        got = sorted(p.key for p in fr.front())
        if keys is None:
            keys = got
        assert got == keys


def test_pareto_offer_semantics_and_roundtrip(tmp_path):
    fr = ParetoFront()
    assert fr.offer(ParetoPoint(1, 1, 1))
    assert not fr.offer(ParetoPoint(2, 2, 2))      # dominated
    assert not fr.offer(ParetoPoint(1, 1, 1))      # duplicate
    assert fr.offer(ParetoPoint(0.5, 2, 1))        # trade-off joins
    assert fr.offer(ParetoPoint(0.4, 0.4, 0.4))    # dominates everything
    assert len(fr) == 1
    fr.save(tmp_path / "front.json")
    back = ParetoFront.load(tmp_path / "front.json")
    assert [p.key for p in back.front()] == [p.key for p in fr.front()]


# ---------------------------------------------------------------------------
# content-addressed cache
# ---------------------------------------------------------------------------


def test_digests_content_addressed():
    a = HwConfig(4, 8, 128, 8, 16, 144, 32)
    b = HwConfig(4, 8, 128, 8, 16, 144, 32)
    assert a is not b and hw_digest(a) == hw_digest(b)
    assert hw_digest(a) != hw_digest(a.replace(pea_col=16))
    g1, g2 = googlenet(1, scale=8), googlenet(1, scale=8)
    assert graph_digest(g1) == graph_digest(g2)
    assert graph_digest(g1) != graph_digest(googlenet(1, scale=4))


def test_eval_cache_roundtrip(tmp_path):
    cache = EvalCache()
    key = EvalCache.key(PAPER_4X4, [googlenet(1, scale=8)])
    assert cache.get(key) is None
    cache.put(key, (1.5, {"g": 2.0}, {"g": 3.0}))
    assert cache.get(key)[0] == 1.5
    assert cache.stats == {"hits": 1, "misses": 1, "entries": 1,
                           "flight_waits": 0}
    cache.save(tmp_path / "cache.json")
    back = EvalCache.load(tmp_path / "cache.json")
    assert back.get(key)[0] == 1.5


# ---------------------------------------------------------------------------
# campaign orchestration
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_workloads():
    return [googlenet(1, scale=8)]


MAPPER_KW = dict(max_optim_iter=1, lm_cap=20, n_wr=2)


def test_campaign_runs_and_checkpoints(tiny_workloads, tmp_path):
    ckpt = tmp_path / "campaign.json"
    camp = Campaign(tiny_workloads, ("random", "gp"), iterations=2,
                    propose_k=4, seed=0, n_sample=64,
                    evaluator_kwargs=dict(mapper_kwargs=MAPPER_KW),
                    checkpoint=ckpt)
    out = camp.run()
    assert set(out.results) == {"random", "gp"}
    assert not out.resumed
    assert out.best().cost > 0
    assert len(out.pareto) >= 1
    state = json.loads(ckpt.read_text())
    assert set(state["strategies"]) == {"random", "gp"}

    # resume: everything is complete, nothing re-evaluates
    camp2 = Campaign(tiny_workloads, ("random", "gp"), iterations=2,
                     propose_k=4, seed=0, n_sample=64,
                     evaluator_kwargs=dict(mapper_kwargs=MAPPER_KW),
                     checkpoint=ckpt)
    out2 = camp2.run()
    assert sorted(out2.resumed) == ["gp", "random"]
    assert out2.cache_stats["misses"] == 0
    for name in ("random", "gp"):
        a = [o.cfg.as_tuple() for o in out.results[name].observations]
        b = [o.cfg.as_tuple() for o in out2.results[name].observations]
        assert a == b


def test_campaign_partial_resume_continues(tiny_workloads, tmp_path):
    ckpt = tmp_path / "partial.json"
    kw = dict(iterations=3, propose_k=4, seed=1, n_sample=64,
              evaluator_kwargs=dict(mapper_kwargs=MAPPER_KW), checkpoint=ckpt)
    camp = Campaign(tiny_workloads, ("random",), **kw)
    out_full = camp.run()
    # simulate a mid-run kill: drop every observation after iteration 0
    state = json.loads(ckpt.read_text())
    state["strategies"]["random"] = [
        o for o in state["strategies"]["random"] if o["iteration"] == 0]
    ckpt.write_text(json.dumps(state))
    camp2 = Campaign(tiny_workloads, ("random",), **kw)
    out = camp2.run()
    assert out.resumed == ["random"]
    iters = {o.iteration for o in out.results["random"].observations}
    assert max(iters) == 2 and 0 in iters
    # the saved iteration-0 observation survives verbatim (and its Pareto
    # contribution is re-offered on resume)
    assert (out.results["random"].observations[0].cfg.as_tuple()
            == out_full.results["random"].observations[0].cfg.as_tuple())
    assert len(out.pareto) >= 1


def test_campaign_checkpoint_rejected_on_workload_change(tiny_workloads,
                                                         tmp_path):
    ckpt = tmp_path / "wl.json"
    kw = dict(iterations=1, propose_k=4, seed=1, n_sample=64,
              evaluator_kwargs=dict(mapper_kwargs=MAPPER_KW), checkpoint=ckpt)
    Campaign(tiny_workloads, ("random",), **kw).run()
    other = Campaign([googlenet(1, scale=4)], ("random",), **kw)
    assert other._load_checkpoint() == {}   # stale workloads: start over


def test_run_dse_feeds_pareto(tiny_workloads):
    from repro.core.dse import WorkloadEvaluator, run_dse
    from repro.core.surrogates import make_strategy
    ev = WorkloadEvaluator(tiny_workloads, mapper_kwargs=MAPPER_KW)
    fr = ParetoFront()
    res = run_dse(make_strategy("random", seed=0, n_sample=64), ev,
                  iterations=2, propose_k=4, pareto=fr)
    n_eval = sum(o.cost is not None for o in res.observations)
    assert fr.offered == n_eval
    assert len(fr) >= (1 if n_eval else 0)


# ---------------------------------------------------------------------------
# scheduler determinism (threaded RNG)
# ---------------------------------------------------------------------------


def test_solve_ilp_ls_seed_reproducible():
    noc = MeshNoc(4, 4)
    sets = [[0, 1, 2, 3, 4, 5, 6, 7], [8, 9, 10, 11, 12, 13, 14, 15]]
    chunks = [1000.0, 2000.0]
    a = solve_ilp_ls(noc, sets, chunks, 3.2e9, 400e6, 1.1, seed=5)
    b = solve_ilp_ls(noc, sets, chunks, 3.2e9, 400e6, 1.1, seed=5)
    assert a.cycles == b.cycles
    assert a.max_link_bytes == b.max_link_bytes
    c = solve_ilp_ls(noc, sets, chunks, 3.2e9, 400e6, 1.1,
                     rng=random.Random(5))
    assert c.cycles == a.cycles


def test_evaluate_mapping_deterministic(tiny_workloads):
    from repro.core.mapper import PimMapper, evaluate_mapping
    mapper = PimMapper(PAPER_4X4, **MAPPER_KW)
    m = mapper.map(tiny_workloads[0])
    r1 = evaluate_mapping(m, seed=3)
    from repro.core.mapper import _sharing_latency
    _sharing_latency.cache_clear()
    r2 = evaluate_mapping(m, seed=3)
    assert r1.latency_s == r2.latency_s
    assert r1.energy_pj == r2.energy_pj
