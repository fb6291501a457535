"""Telemetry layer: Chrome-trace tracer, metrics registry, bench gate.

Covers the trace export contract (valid Chrome trace event format: required
keys, non-negative durations, monotonic timestamps per thread row), span
nesting across concurrent threads, the zero-cost disabled path, checkpoint
discard diagnostics, and the BENCH regression-gate comparison rules.
"""

import json
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))  # benchmarks/

from repro.core.workloads import googlenet
from repro.engine.campaign import Campaign
from repro.obs import metrics as obs_metrics
from repro.obs import trace
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Tracer

TINY_EVAL_KW = dict(mapper_kwargs=dict(max_optim_iter=1, lm_cap=20, n_wr=2))


# -- tracer ------------------------------------------------------------------

def test_disabled_span_is_shared_noop():
    assert trace.current() is None
    s1 = trace.span("map", configs=3)
    s2 = trace.span("schedule")
    assert s1 is s2  # one singleton: nothing allocated when tracing is off
    with s1 as args:
        assert args == {}
    trace.instant("nothing")  # must not raise with no tracer
    trace.set_thread_name("nobody")


def test_traced_decorator_disabled_is_passthrough():
    calls = []

    @trace.traced("work", argspec=lambda n: {"n": n})
    def work(n):
        calls.append(n)
        return n * 2

    assert work(3) == 6
    assert calls == [3]


def _required_x_keys(ev):
    return all(k in ev for k in ("name", "cat", "ph", "ts", "dur",
                                 "pid", "tid", "args"))


def test_chrome_trace_format_valid(tmp_path):
    t = Tracer()
    with trace.activate(t):
        trace.set_thread_name("main")
        with trace.span("outer", cat="dse", k=4) as sp:
            with trace.span("inner", cat="engine"):
                pass
            sp["outcome"] = "hit"
        trace.instant("marker", reason="test")
    out = t.save(tmp_path / "trace.json")
    doc = json.loads(out.read_text())  # round-trips as JSON

    evs = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    meta = [e for e in evs if e["ph"] == "M"]
    spans = [e for e in evs if e["ph"] == "X"]
    inst = [e for e in evs if e["ph"] == "i"]
    assert {e["name"] for e in meta} >= {"process_name", "thread_name"}
    # metadata leads the file so viewers name rows before drawing spans
    assert evs[: len(meta)] == meta

    assert {e["name"] for e in spans} == {"outer", "inner"}
    for e in spans:
        assert _required_x_keys(e)
        assert e["dur"] >= 0
        assert e["ts"] >= 0
    assert len(inst) == 1 and inst[0]["args"]["reason"] == "test"

    # mutating the yielded dict lands in the recorded event args
    outer = next(e for e in spans if e["name"] == "outer")
    assert outer["args"] == {"k": 4, "outcome": "hit"}

    # monotonic ts within each tid, in file order
    by_tid = {}
    for e in spans + inst:
        by_tid.setdefault(e["tid"], []).append(e["ts"])
    for ts in by_tid.values():
        assert ts == sorted(ts)

    # nesting: inner is contained in outer's [ts, ts+dur] window
    inner = next(e for e in spans if e["name"] == "inner")
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6


def test_span_threads_get_distinct_rows():
    t = Tracer()
    barrier = threading.Barrier(2)

    def worker(label):
        trace.set_thread_name(label)
        with trace.span("outer", who=label):
            barrier.wait()  # both spans provably concurrent
            with trace.span("inner", who=label):
                time.sleep(0.001)

    with trace.activate(t):
        threads = [threading.Thread(target=worker, args=(f"w{i}",))
                   for i in range(2)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()

    spans = [e for e in t.events() if e["ph"] == "X"]
    tids = {e["args"]["who"]: e["tid"] for e in spans}
    assert tids["w0"] != tids["w1"]
    for who in ("w0", "w1"):
        mine = [e for e in spans if e["args"]["who"] == who]
        outer = next(e for e in mine if e["name"] == "outer")
        inner = next(e for e in mine if e["name"] == "inner")
        assert outer["tid"] == inner["tid"]
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    names = [e for e in t.events()
             if e["ph"] == "M" and e["name"] == "thread_name"]
    assert {e["args"]["name"] for e in names} == {"w0", "w1"}



def test_spans_share_one_clock_with_the_profiler(tmp_path):
    """Each span's ``ts`` and its annotation in the profiler's trace differ
    by one constant offset, so the Chrome file lays over a device trace."""
    import jax
    before = time.perf_counter_ns()
    t = Tracer()
    after = time.perf_counter_ns()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.activate(t):
            for i in range(5):
                with trace.span(f"clock_probe_{i}"):
                    time.sleep(0.004)
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    files = sorted(tmp_path.rglob("*.xplane.pb"))
    assert files
    starts = {}
    for plane in jax.profiler.ProfileData.from_file(str(files[-1])).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("clock_probe_"):
                    starts[ev.name] = ev.start_ns
    spans = [e for e in t.events() if e["ph"] == "X"]
    assert {e["name"] for e in spans} == set(starts)
    offsets = [starts[e["name"]] - e["ts"] * 1e3 for e in spans]
    assert max(offsets) - min(offsets) < 0.1e6      # 0.1 ms
    # the origin on the host's monotonic clock travels with the file
    origin = t.to_chrome()["otherData"]["origin_perf_counter_ns"]
    assert before <= origin <= after


# -- metrics -----------------------------------------------------------------

def test_metrics_registry_instruments():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(2)
    reg.gauge("g").set(7)
    reg.gauge("best").min(5.0)
    reg.gauge("best").min(9.0)  # larger: ignored
    for v in (1.0, 3.0):
        reg.histogram("h").observe(v)
    snap = reg.snapshot()
    assert snap["c"] == 3
    assert snap["g"] == 7
    assert snap["best"] == 5.0
    assert snap["h"] == {"count": 2, "sum": 4.0, "min": 1.0, "max": 3.0,
                         "mean": 2.0}
    with pytest.raises(TypeError):
        reg.gauge("c")  # name already registered as a Counter
    reg.reset()
    assert reg.snapshot() == {}


def test_tuner_bucket_metrics():
    from repro.engine.tuner_train import _record_bucket
    obs_metrics.METRICS.reset()
    _record_bucket("filter", np.zeros(8), np.array([1.0] * 5 + [0.0] * 3))
    snap = obs_metrics.METRICS.snapshot()
    assert snap["tuner.bucket.filter"] == 8
    assert snap["tuner.bucket_fill.filter"]["mean"] == pytest.approx(5 / 8)
    assert snap["tuner.padded_rows.filter"] == 3
    obs_metrics.METRICS.reset()


# -- campaign checkpoint discard diagnostics ---------------------------------

def _tiny_campaign(tmp_path, reg, tracer=None):
    return Campaign([googlenet(1, scale=8)], ("random",), iterations=2,
                    propose_k=2, n_sample=32, evaluator_kwargs=TINY_EVAL_KW,
                    checkpoint=tmp_path / "ck.json", metrics=reg,
                    tracer=tracer)


def test_checkpoint_discard_unreadable(tmp_path):
    reg = MetricsRegistry()
    camp = _tiny_campaign(tmp_path, reg)
    (tmp_path / "ck.json").write_text('{"fingerprint": "trunca')
    with pytest.warns(RuntimeWarning, match="unreadable"):
        assert camp._load_checkpoint() == {}
    snap = reg.snapshot()
    assert snap["campaign.checkpoint_discarded"] == 1
    assert snap["campaign.checkpoint_discarded.unreadable"] == 1


def test_checkpoint_discard_fingerprint_mismatch(tmp_path):
    reg = MetricsRegistry()
    camp = _tiny_campaign(tmp_path, reg)
    (tmp_path / "ck.json").write_text(json.dumps(
        {"fingerprint": "not-this-campaign", "strategies": {}}))
    with pytest.warns(RuntimeWarning, match="fingerprint_mismatch"):
        assert camp._load_checkpoint() == {}
    snap = reg.snapshot()
    assert snap["campaign.checkpoint_discarded.fingerprint_mismatch"] == 1


def test_checkpoint_absent_is_silent(tmp_path):
    reg = MetricsRegistry()
    camp = _tiny_campaign(tmp_path, reg)
    assert camp._load_checkpoint() == {}
    assert "campaign.checkpoint_discarded" not in reg.snapshot()


# -- end-to-end: traced campaign smoke ---------------------------------------

def test_campaign_emits_spans_and_metrics(tmp_path):
    reg = MetricsRegistry()
    tracer = Tracer()
    camp = _tiny_campaign(tmp_path, reg, tracer=tracer)
    out = camp.run()

    assert set(out.wall_s) == {"random"}
    assert out.wall_s["random"] >= out.timings_s["random"] >= 0.0
    assert out.metrics["eval_cache.entries"] >= 1
    assert out.metrics["pareto.size"] == len(out.pareto)

    names = {e["name"] for e in tracer.events() if e["ph"] == "X"}
    assert {"strategy", "iteration", "propose", "evaluate", "map",
            "checkpoint"} <= names
    evaluate = [e for e in tracer.events()
                if e["ph"] == "X" and e["name"] == "evaluate"]
    assert all(e["args"].get("cache") in ("local_hit", "content_hit", "miss")
               for e in evaluate)

    # the checkpoint carries the registry snapshot for post-mortems
    state = json.loads((tmp_path / "ck.json").read_text())
    assert state["metrics"]["eval_cache.entries"] >= 1

    # saved trace loads as valid Chrome trace format
    doc = json.loads(tracer.save(tmp_path / "t.json").read_text())
    assert all(_required_x_keys(e) and e["dur"] >= 0
               for e in doc["traceEvents"] if e["ph"] == "X")


# -- bench gate --------------------------------------------------------------

def _bench(mode="smoke", **gates):
    return {"schema": "nicepim-bench/1", "bench_id": 6, "mode": mode,
            "gates": {k: {"value": v, "tolerance": 0.25,
                          "higher_is_better": True}
                      for k, v in gates.items()}}


def test_bench_gate_within_tolerance_passes():
    from benchmarks.bench_gate import compare
    fails, _ = compare(_bench(engine=4.0), _bench(engine=5.0))
    assert fails == []  # 4.0 >= 5.0 * (1 - 0.25)


def test_bench_gate_regression_fails():
    from benchmarks.bench_gate import compare
    fails, lines = compare(_bench(engine=3.0), _bench(engine=5.0))
    assert fails == ["engine"]
    assert any("REGRESSED" in ln for ln in lines)


def test_bench_gate_new_and_removed_gates_never_fail():
    from benchmarks.bench_gate import compare
    fails, lines = compare(_bench(fresh=1.0), _bench(retired=9.0))
    assert fails == []
    assert len(lines) == 2


def test_bench_gate_cli_skips(tmp_path, capsys):
    from benchmarks.bench_gate import main
    cur = tmp_path / "cur.json"
    cur.write_text(json.dumps(_bench("smoke", engine=1.0)))
    # no baseline: clean skip
    assert main(["--current", str(cur)]) == 0
    assert "skipping" in capsys.readouterr().out
    # mode mismatch: clean skip
    base = tmp_path / "base.json"
    base.write_text(json.dumps(_bench("full", engine=9.0)))
    assert main(["--current", str(cur), "--baseline", str(base)]) == 0
    assert "mode mismatch" in capsys.readouterr().out
    # comparable baseline with a regression: exit 1
    base.write_text(json.dumps(_bench("smoke", engine=9.0)))
    assert main(["--current", str(cur), "--baseline", str(base)]) == 1
