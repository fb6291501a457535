"""Phase spans inside the mapper, device costing and the Data-Scheduler.

A traced ``WorkloadEvaluator.evaluate_batch`` on GoogLeNet at 56x56, with
the benchmark cell's mapper settings and cross-config scheduler prefill,
on the overlap path and under ``serial_dispatch()``:

* every phase span is emitted, with its arguments;
* the spans nest as the phases do (mapper phases inside ``map_wave``,
  device waits inside the phase that pulls, deferred scheduling and
  accounting inside ``overlap_drain``);
* no span is open across a generator ``yield``;
* results and mappings are bitwise equal with and without a tracer.

The single-config walk (``PimMapper.map``) emits the mapper's phase spans
too, with its synchronous device pulls as ``device_wait``.
"""

from contextlib import contextmanager, nullcontext

import pytest

from repro.core import mapper as mapper_mod
from repro.core.dse import WorkloadEvaluator
from repro.core.hardware import PAPER_4X4, PAPER_BEST, HwConfig
from repro.core.layout import DataLayout
from repro.core.mapper import PimMapper, clear_mapper_caches
from repro.core.workloads import googlenet
from repro.engine import scheduler_opt
from repro.engine.batch_cost import PartSpec, _prep_configs, _prep_specs
from repro.obs import trace
from repro.obs.trace import Tracer

# the benchmark cell's mapper cuts (bench/configs/googlenet.json)
MAPPER_KW = dict(max_optim_iter=1, lm_cap=60, n_wr=3)
CFGS = [PAPER_4X4, PAPER_BEST,
        HwConfig.from_tuple((2, 8, 32, 32, 64, 64, 64))]

MAPPER_SPANS = ("cand_dispatch", "cand_build", "dp_solve", "dl_dispatch",
                "dl_optimize")
ARGS = {"cand_dispatch": {"keys", "built"}, "cand_build": {"tables"},
        "dp_solve": {"segments"}, "dl_dispatch": {"specs"},
        "dl_optimize": {"specs"}, "device_wait": {"what"},
        "sched_problems": set(), "accounting": set(),
        "dispatch_paired": {"pairs", "bytes"}, "schedule": {"problems"}}


class StackTracer(Tracer):
    """A tracer that also knows which spans are open right now."""

    def __init__(self):
        super().__init__()
        self.open: list[str] = []

    @contextmanager
    def span(self, name, cat="dse", **args):
        self.open.append(name)
        try:
            with super().span(name, cat=cat, **args) as a:
                yield a
        finally:
            self.open.pop()


def _checked(inner, tracer, seen, out=None):
    """Drive ``inner``; at each of its yields note whether the spans open
    are those that were open when it was resumed."""
    while True:
        before = list(tracer.open) if tracer else None
        try:
            item = next(inner)
        except StopIteration as stop:
            if out is not None:
                out.extend(stop.value)
            return stop.value
        if tracer:
            seen.append(tracer.open == before)
        yield item


def _run(monkeypatch, tracer, overlap):
    """One evaluate_batch from cold memos; returns (results, mappings,
    spans, yield checks, bucket dispatches)."""
    clear_mapper_caches()
    mapper_mod._sharing_latency.cache_clear()
    seen, mappings, packs = [], [], []
    phases = PimMapper.map_many_phases
    finish = WorkloadEvaluator._finish_wave
    pack = scheduler_opt._pack_solve
    with monkeypatch.context() as mp:
        mp.setattr(PimMapper, "map_many_phases",
                   lambda self, *a, **kw: _checked(
                       phases(self, *a, **kw), tracer, seen, mappings))
        mp.setattr(WorkloadEvaluator, "_finish_wave",
                   lambda self, *a: _checked(finish(self, *a), tracer, seen))

        def recording_pack(setups, **kw):
            if tracer:
                packs.append((tracer._now_us(), kw["r_pad"],
                              len(setups) * len(setups[0].inits)))
            return pack(setups, **kw)
        mp.setattr(scheduler_opt, "_pack_solve", recording_pack)
        ev = WorkloadEvaluator([googlenet(1, scale=4)],
                               mapper_kwargs=MAPPER_KW, batch_prefill=True,
                               overlap=overlap)
        ctx = trace.activate(tracer) if tracer else nullcontext()
        with ctx:
            res = ev.evaluate_batch(CFGS)
    spans = ([e for e in tracer.events() if e["ph"] == "X"]
             if tracer else [])
    return res, mappings, spans, seen, packs


@pytest.fixture(scope="module")
def runs():
    mp = pytest.MonkeyPatch()
    try:
        plain = _run(mp, None, overlap=True)
        over = _run(mp, StackTracer(), overlap=True)
        serial = _run(mp, StackTracer(), overlap=False)
    finally:
        mp.undo()
    return {"plain": plain, "overlap": over, "serial": serial}


def _within(inner, outer) -> bool:
    return (outer["ts"] - 1e-3 <= inner["ts"]
            and inner["ts"] + inner["dur"]
            <= outer["ts"] + outer["dur"] + 1e-3)


def _enclosing(s, spans) -> set:
    return {o["name"] for o in spans
            if o is not s and o["tid"] == s["tid"] and _within(s, o)}


@pytest.mark.parametrize("path", ["overlap", "serial"])
def test_every_phase_span_is_emitted_with_its_arguments(runs, path):
    spans = runs[path][2]
    for name, keys in ARGS.items():
        mine = [s for s in spans if s["name"] == name]
        assert mine, (path, name)
        for s in mine:
            assert keys <= set(s["args"]), (path, name, s["args"])
    waits = {s["args"]["what"] for s in spans if s["name"] == "device_wait"}
    assert waits == {"batch_cost", "fold_keys", "scan_solve"}
    # the host arrays of one padded pair: per-row layer fields and the
    # pair's config fields, no [L, T] tiling grid
    lay, _ = _prep_specs([PartSpec(googlenet(1).layers[0],
                                   DataLayout("BHWC"), DataLayout("BHWC"))])
    cfg, _ = _prep_configs([PAPER_BEST])
    pair_bytes = sum(v.nbytes for v in (*lay.values(), *cfg.values()))
    for s in spans:
        if s["name"] == "dispatch_paired":
            assert s["args"]["pairs"] > 0
            # each block pads its pairs to a power of two, floor 128
            n = s["args"]["bytes"] // pair_bytes
            assert n * pair_bytes == s["args"]["bytes"], (path, s["args"])
            assert s["args"]["pairs"] <= n, (path, s["args"])
            assert n < 2 * s["args"]["pairs"] + 128 * s["args"]["buckets"]
        if s["name"] == "cand_dispatch":
            assert 0 <= s["args"]["built"] <= s["args"]["keys"]


def test_spans_nest_as_the_phases_do(runs):
    spans = runs["overlap"][2]
    for s in spans:
        up = _enclosing(s, spans)
        if s["name"] in MAPPER_SPANS:
            assert "map_wave" in up, s
        elif s["name"] == "device_wait":
            if s["args"]["what"] == "batch_cost":
                assert up & {"cand_build", "dl_optimize"}, (s, up)
            else:
                assert "schedule" in up, (s, up)
        elif s["name"] in ("sched_problems", "accounting"):
            assert "overlap_drain" in up, (s, up)


@pytest.mark.parametrize("path", ["overlap", "serial"])
def test_no_span_is_open_across_a_yield(runs, path):
    seen = runs[path][3]
    assert seen and all(seen), seen


@pytest.mark.parametrize("path", ["overlap", "serial"])
def test_results_and_mappings_bitwise_equal_with_a_tracer(runs, path):
    res0, maps0 = runs["plain"][:2]
    res, maps = runs[path][:2]
    assert res == res0
    assert len(maps) == len(maps0) == len(CFGS)
    for a, b in zip(maps, maps0):
        assert a.sm == b.sm
        assert a.est_latency_s == b.est_latency_s
        assert set(a.choices) == set(b.choices)
        for name, ca in a.choices.items():
            cb = b.choices[name]
            assert (ca.lm, ca.wr, ca.region, ca.dl_in, ca.dl_out,
                    ca.perf_s) == (cb.lm, cb.wr, cb.region, cb.dl_in,
                                   cb.dl_out, cb.perf_s), name


def _walk(tracer):
    """One single-config ``PimMapper.map`` from cold memos."""
    clear_mapper_caches()
    pm = PimMapper(PAPER_4X4, **MAPPER_KW)
    g = googlenet(1, scale=4)
    with trace.activate(tracer) if tracer else nullcontext():
        return pm.map(g)


def test_single_config_walk_emits_the_mapper_phases():
    plain = _walk(None)
    t = Tracer()
    m = _walk(t)
    clear_mapper_caches()
    spans = [e for e in t.events() if e["ph"] == "X"]
    names = {s["name"] for s in spans}
    assert set(MAPPER_SPANS) - {"dl_dispatch"} <= names
    for s in spans:
        assert ARGS.get(s["name"], set()) <= set(s["args"]), s
        up = _enclosing(s, spans)
        if s["name"] in MAPPER_SPANS:
            assert "map" in up, (s, up)
        if s["name"] == "device_wait":
            assert up & {"cand_build", "dl_optimize"}, (s, up)
    # the DL sweep's synchronous pull is a device wait, not costing host
    assert any(s["name"] == "device_wait"
               and "dl_optimize" in _enclosing(s, spans) for s in spans)
    (dl,) = [s for s in spans if s["name"] == "dl_optimize"]
    assert dl["args"]["specs"] > 0
    assert m.est_latency_s == plain.est_latency_s
    for name, ca in m.choices.items():
        cb = plain.choices[name]
        assert (ca.lm, ca.wr, ca.region, ca.dl_in, ca.dl_out, ca.perf_s) \
            == (cb.lm, cb.wr, cb.region, cb.dl_in, cb.dl_out, cb.perf_s)
