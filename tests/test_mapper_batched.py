"""Batched mapper backend: parity with the scalar path, knapsack kernel,
spec-chunked engine invariance, scheduler delta updates, cache hooks."""

import random

import numpy as np
import pytest
from hypothesis_compat import given, settings, strategies as st

from test_mapper import toy_net

from repro.core import mapper as mapper_mod
from repro.core.hardware import PAPER_4X4, PAPER_16X16, PAPER_BEST
from repro.core.layout import DataLayout
from repro.core.mapper import (PimMapper, RegionTable, clear_mapper_caches,
                               evaluate_mapping)
from repro.core.noc import MeshNoc
from repro.core.partition import (comm_estimate, comm_estimate_batch,
                                  enumerate_lms, wr_candidates)
from repro.core.scheduler import (_all_transfers, _apply_2opt, _move_edges,
                                  _propose_moves, solve_ilp_ls)
from repro.core.workloads import googlenet

RTOL = 1e-6


def _mapping_pair(graph, hw, **kw):
    clear_mapper_caches()
    ms = PimMapper(hw, backend="scalar", **kw).map(graph)
    clear_mapper_caches()
    mb = PimMapper(hw, backend="batched", **kw).map(graph)
    return ms, mb


@pytest.mark.parametrize("graph,hw", [
    (toy_net(), PAPER_4X4),            # branchy graph
    (toy_net(), PAPER_16X16),
    (googlenet(1, scale=8), PAPER_BEST),
])
def test_backend_parity_identical_mapping(graph, hw):
    ms, mb = _mapping_pair(graph, hw, max_optim_iter=2)
    assert ms.sm == mb.sm
    assert set(ms.choices) == set(mb.choices)
    for name, cs in ms.choices.items():
        cb = mb.choices[name]
        assert (cs.lm, cs.wr, cs.region) == (cb.lm, cb.wr, cb.region), name
        assert (cs.dl_in, cs.dl_out) == (cb.dl_in, cb.dl_out), name
        assert cs.perf_s == pytest.approx(cb.perf_s, rel=RTOL)
        assert cs.size_bytes == pytest.approx(cb.size_bytes, rel=RTOL)
    assert ms.est_latency_s == pytest.approx(mb.est_latency_s, rel=RTOL)


def test_backend_parity_evaluate_mapping():
    g = toy_net()
    ms, mb = _mapping_pair(g, PAPER_4X4, max_optim_iter=2)
    rs = evaluate_mapping(ms, seed=1)
    mapper_mod._sharing_latency.cache_clear()
    rb = evaluate_mapping(mb, seed=1)
    assert rs.latency_s == pytest.approx(rb.latency_s, rel=RTOL)
    assert rs.energy_pj == pytest.approx(rb.energy_pj, rel=RTOL)
    for a, b in zip(rs.layers, rb.layers):
        assert a.name == b.name
        assert a.latency_s == pytest.approx(b.latency_s, rel=RTOL)


def test_unknown_backend_rejected():
    with pytest.raises(ValueError):
        PimMapper(PAPER_4X4, backend="gpu")


def test_candidate_tables_match_scalar():
    """The batched prefetch reproduces _layer_candidates tuples exactly."""
    hw = PAPER_4X4
    pm = PimMapper(hw, backend="batched", lm_cap=40, n_wr=3)
    layers = [l for l in googlenet(1, scale=8).layers if l.is_heavy][:6]
    clear_mapper_caches()
    for l in layers:
        din, dout = pm._default_dl(l.C), pm._default_dl(l.K)
        got = pm._candidates(l, 4, 4, din, dout)
        ref = mapper_mod._layer_candidates(hw, l, 4, 4, din, dout, 3, 40)
        assert len(got) == len(ref)
        for (wg, pg, sg, lg), (wr, pr, sr, lr) in zip(got, ref):
            assert (wg, lg) == (wr, lr)
            assert pg == pytest.approx(pr, rel=RTOL)
            assert sg == pytest.approx(sr, rel=RTOL)


# ---------------------------------------------------------------------------
# vectorized comm estimate
# ---------------------------------------------------------------------------


def test_comm_estimate_batch_bitwise():
    l = googlenet(1, scale=8).layers[2]
    hw = PAPER_16X16
    pair_lms, pair_wrs = [], []
    for lm in enumerate_lms(l, 4, 8, cap=50):
        for wr in wr_candidates(l, lm, 4):
            pair_lms.append(lm)
            pair_wrs.append(wr)
    lat, en, stored = comm_estimate_batch(l, hw, pair_lms, pair_wrs)
    for p, (lm, wr) in enumerate(zip(pair_lms, pair_wrs)):
        ce = comm_estimate(l, lm, wr, hw)
        assert lat[p] == ce.latency_s
        assert en[p] == ce.energy_pj
        assert stored[p] == ce.weight_bytes_per_node


def test_comm_estimate_batch_aux_layer_zero():
    g = toy_net()
    aux = g.layer("cat")
    lms = list(enumerate_lms(aux, 2, 2, cap=4))
    lat, en, stored = comm_estimate_batch(aux, PAPER_4X4, lms, [1] * len(lms))
    assert not lat.any() and not en.any() and not stored.any()


# ---------------------------------------------------------------------------
# array-form knapsack: numpy vs Pallas reduction
# ---------------------------------------------------------------------------


@st.composite
def knapsack_instance(draw):
    n_layers = draw(st.integers(1, 4))
    layers = []
    for i in range(n_layers):
        cands = [(c, draw(st.floats(0.1, 10.0)),
                  draw(st.integers(0, 6)) * 1000.0, None)
                 for c in range(draw(st.integers(1, 3)))]
        cands.sort(key=lambda t: -t[2])
        layers.append((f"l{i}", tuple(cands)))
    return layers, draw(st.integers(4, 12))


@given(knapsack_instance())
@settings(max_examples=25)
def test_knapsack_pallas_matches_numpy(inst):
    layers, units = inst
    a = RegionTable(layers, units, 1000.0, reduce="numpy")
    b = RegionTable(layers, units, 1000.0, reduce="pallas")
    np.testing.assert_array_equal(a.perf, b.perf)
    np.testing.assert_array_equal(a.choice, b.choice)
    np.testing.assert_array_equal(a.eff, b.eff)
    assert a.backtrack(units) == b.backtrack(units)


def test_knapsack_pallas_matches_numpy_seeded():
    """Deterministic twin of the property test (runs without hypothesis)."""
    rng = random.Random(11)
    for _ in range(30):
        layers = []
        for i in range(rng.randint(1, 5)):
            cands = [(c, rng.uniform(0.1, 10.0), rng.randint(0, 8) * 1000.0,
                      None) for c in range(rng.randint(1, 4))]
            cands.sort(key=lambda t: -t[2])
            layers.append((f"l{i}", tuple(cands)))
        units = rng.randint(4, 16)
        a = RegionTable(layers, units, 1000.0, reduce="numpy")
        b = RegionTable(layers, units, 1000.0, reduce="pallas")
        np.testing.assert_array_equal(a.perf, b.perf)
        np.testing.assert_array_equal(a.choice, b.choice)
        assert a.backtrack(units) == b.backtrack(units)


# ---------------------------------------------------------------------------
# segment min-plus convolution: array form vs the old sequential loop
# ---------------------------------------------------------------------------


INF = float("inf")


def _minplus_ref(tab, best):
    """The removed O(units^2) per-prefix Python loop, verbatim."""
    units = len(tab) - 1
    ntab = np.full(units + 1, INF)
    arg_i = np.full(units + 1, -1, np.int32)
    for i in range(units + 1):
        if not np.isfinite(tab[i]):
            continue
        cand = tab[i] + best[:units + 1 - i]
        seg = ntab[i:]
        better = cand < seg
        ntab[i:] = np.where(better, cand, seg)
        arg_i[i:][better] = i
    return ntab, arg_i


def _monotone_fill_ref(tab, arg_i):
    """The removed sequential monotone fill, verbatim."""
    tab = tab.copy()
    arg_i = arg_i.copy()
    for cap in range(1, len(tab)):
        if tab[cap - 1] < tab[cap]:
            tab[cap] = tab[cap - 1]
            arg_i[cap] = arg_i[cap - 1]
    return tab, arg_i


def _rand_minplus_case(rng, u):
    tab = rng.uniform(0.1, 5.0, u + 1)
    best = rng.uniform(0.1, 5.0, u + 1)
    tab[rng.random(u + 1) < 0.3] = INF
    best[rng.random(u + 1) < 0.3] = INF
    # quantize so ties actually occur and exercise the first-argmin rule
    tab = np.where(np.isfinite(tab), np.round(tab, 1), tab)
    best = np.where(np.isfinite(best), np.round(best, 1), best)
    return tab, best


@pytest.mark.parametrize("reduce", ["numpy", "pallas"])
def test_minplus_convolve_matches_sequential_loop(reduce):
    rng = np.random.default_rng(5)
    for _ in range(40 if reduce == "numpy" else 10):
        u = int(rng.integers(1, 48))
        tab, best = _rand_minplus_case(rng, u)
        ref_tab, ref_arg = _minplus_ref(tab, best)
        got_tab, got_arg = mapper_mod.minplus_convolve(tab, best,
                                                       reduce=reduce)
        np.testing.assert_array_equal(ref_tab, got_tab)
        np.testing.assert_array_equal(ref_arg, got_arg)


def test_minplus_monotone_fill_matches_sequential():
    """The vectorized fill in _solve_sm_lm_wr == the old in-place loop."""
    rng = np.random.default_rng(7)
    for _ in range(40):
        u = int(rng.integers(1, 48))
        tab, best = _rand_minplus_case(rng, u)
        ntab, arg_i = mapper_mod.minplus_convolve(tab, best, reduce="numpy")
        ref_tab, ref_arg = _monotone_fill_ref(ntab, arg_i)
        run = np.minimum.accumulate(ntab)
        src = np.maximum.accumulate(
            np.where(ntab <= run, np.arange(u + 1), 0))
        np.testing.assert_array_equal(ref_tab, run)
        np.testing.assert_array_equal(ref_arg, arg_i[src])


def test_minplus_rows_kernel_matches_numpy():
    from repro.kernels import dse_eval
    from repro.runtime import x64
    rng = np.random.default_rng(9)
    a = rng.uniform(0.0, 4.0, 33)
    a[rng.random(33) < 0.25] = INF
    b = rng.uniform(0.0, 4.0, (17, 33))
    b[rng.random((17, 33)) < 0.25] = INF
    with x64():  # the DP runs the kernel in f64, like the engine
        mn, idx = dse_eval.minplus_rows(a, b, block_r=4)
    scores = a[None, :] + b
    np.testing.assert_array_equal(np.asarray(mn), scores.min(axis=1))
    np.testing.assert_array_equal(np.asarray(idx), scores.argmin(axis=1))


def test_minplus_bad_reduce_rejected():
    with pytest.raises(ValueError):
        mapper_mod.minplus_convolve(np.zeros(4), np.zeros(4), reduce="cuda")


def test_backtrack_zero_candidate_layer_contained():
    # regression: a layer with an empty candidate tuple used to raise
    # ValueError (min() of empty sequence) in backtrack and IndexError in
    # the caller — now it is simply left unpicked
    layers = [("ok", ((0, 1.0, 1000.0, None), (1, 2.0, 0.0, None))),
              ("none", ())]
    tab = RegionTable(layers, 8, 1000.0)
    picks = tab.backtrack(8)
    assert "none" not in picks
    assert picks["ok"] in (0, 1)
    # an all-empty table stays contained too
    tab2 = RegionTable([("none", ())], 8, 1000.0)
    assert tab2.backtrack(8) == {}


def test_knapsack_empty_candidate_list_is_infeasible():
    # a layer with no legal LM contributes an all-INF row (old per-candidate
    # loop semantics), not a crash in the array-form reduction
    layers = [("ok", ((0, 1.0, 1000.0, None),)), ("none", ())]
    tab = RegionTable(layers, 8, 1000.0)
    assert not np.isfinite(tab.perf).any()
    assert (tab.choice[1] == -1).all()


def test_knapsack_bad_reduce_rejected():
    with pytest.raises(ValueError):
        RegionTable([("l0", ((0, 1.0, 0.0, None),))], 4, 1.0, reduce="cuda")


# ---------------------------------------------------------------------------
# spec-chunked engine path
# ---------------------------------------------------------------------------


def test_batch_part_cost_spec_chunk_invariant():
    from repro.engine.batch_cost import PartSpec, batch_part_cost
    layers = [l for l in googlenet(1, scale=4).layers if l.is_heavy][:9]
    specs = [PartSpec(l, DataLayout("BCHW", 4), DataLayout("BHWC"))
             for l in layers]
    a = batch_part_cost([PAPER_4X4, PAPER_BEST], specs)
    b = batch_part_cost([PAPER_4X4, PAPER_BEST], specs, spec_chunk=4)
    np.testing.assert_allclose(a.latency_s, b.latency_s, rtol=0)
    np.testing.assert_allclose(a.energy_pj, b.energy_pj, rtol=0)
    np.testing.assert_array_equal(a.tiling, b.tiling)


# ---------------------------------------------------------------------------
# batched 2-opt scheduler: delta updates + determinism
# ---------------------------------------------------------------------------


def test_move_deltas_match_rebuild():
    rng = random.Random(3)
    noc = MeshNoc(4, 4)
    for _ in range(40):
        n = rng.randint(4, 10)
        nodes = rng.sample(range(16), n)
        chunk = 64.0
        w = (n - 1) * chunk
        cyc = list(nodes)
        inc = noc.route_incidence(tuple(sorted(nodes)))
        loads = noc.link_loads_np(_all_transfers([cyc], [chunk]))
        moves = _propose_moves([cyc], rng, 3)
        for (si, i, j) in moves:
            rem, add = _move_edges(cyc, i, j)
            delta = np.zeros(loads.size)
            for sign, edges in ((1.0, add), (-1.0, rem)):
                ids = [inc[e] for e in edges if e[0] != e[1]]
                if ids:
                    np.add.at(delta, np.concatenate(ids), sign)
            cyc = _apply_2opt(cyc, i, j)
            loads = loads + w * delta
            ref = noc.link_loads_np(_all_transfers([cyc], [chunk]))
            np.testing.assert_allclose(loads, ref)


def test_batched_ls_still_deterministic_and_competitive():
    noc = MeshNoc(4, 4)
    sets = [[0, 1, 2, 3, 4, 5, 6, 7], [8, 9, 10, 11, 12, 13, 14, 15]]
    chunks = [4096.0, 4096.0]
    a = solve_ilp_ls(noc, sets, chunks, 3.2e9, 400e6, 1.1, seed=9)
    b = solve_ilp_ls(noc, sets, chunks, 3.2e9, 400e6, 1.1, seed=9)
    assert a.cycles == b.cycles and a.max_link_bytes == b.max_link_bytes
    # a snake seed alone achieves this bound; LS must not end up worse
    from repro.core.scheduler import solve_tsp
    tsp = solve_tsp(noc, sets, chunks, 3.2e9, 400e6, 1.1)
    assert a.max_link_bytes <= tsp.max_link_bytes + 1e-6


# ---------------------------------------------------------------------------
# bounded caches + the campaign clear hook
# ---------------------------------------------------------------------------


def test_bounded_cache_evicts():
    c = mapper_mod._BoundedCache(maxsize=3)
    for i in range(5):
        c.put(i, i)
    assert len(c._d) == 3
    assert 0 not in c and 4 in c


def test_clear_mapper_caches_drops_everything():
    g = toy_net()
    PimMapper(PAPER_4X4, max_optim_iter=1, backend="batched").map(g)
    assert len(mapper_mod._BATCH_CANDS._d) > 0
    assert len(mapper_mod._NODE_LAT._d) > 0
    clear_mapper_caches()
    assert len(mapper_mod._BATCH_CANDS._d) == 0
    assert len(mapper_mod._NODE_LAT._d) == 0
    assert len(mapper_mod._CAND_STRUCT._d) == 0
    assert mapper_mod._layer_candidates.cache_info().currsize == 0


def test_evaluator_clears_between_configs():
    from repro.core.dse import WorkloadEvaluator
    ev = WorkloadEvaluator([googlenet(1, scale=8)],
                           mapper_kwargs=dict(max_optim_iter=1, lm_cap=20,
                                              n_wr=2),
                           clear_caches_between_configs=True)
    cost, _, _ = ev(PAPER_4X4)
    assert cost > 0
    assert len(mapper_mod._BATCH_CANDS._d) == 0
    assert mapper_mod._sharing_latency.cache_info().currsize == 0


def test_evaluator_backend_keys_content_cache():
    from repro.core.dse import WorkloadEvaluator
    wl = [googlenet(1, scale=8)]
    kw = dict(max_optim_iter=1, lm_cap=20, n_wr=2)
    a = WorkloadEvaluator(wl, mapper_kwargs=kw, mapper_backend="batched")
    b = WorkloadEvaluator(wl, mapper_kwargs=kw, mapper_backend="scalar")
    assert a.mapper_kwargs["backend"] == "batched"
    assert a._content_key(PAPER_4X4) != b._content_key(PAPER_4X4)
