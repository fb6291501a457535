"""The reduction from trace rows and program spans to per-layer numbers."""

import json
from pathlib import Path

import numpy as np

import spec
import tracing

FIXTURE = Path(__file__).parent / "fixtures" / "trace_excerpt.json"


def _dev(name, start, dur):
    return {"plane": "/device:TPU:0", "line": "XLA Modules", "name": name,
            "start": start, "dur": dur}


def _host(name, start, dur):
    return {"plane": "/host:CPU", "line": "main", "name": name,
            "start": start, "dur": dur}


def test_union_and_busy():
    assert tracing.union([(0, 10), (5, 15), (20, 30), (30, 31)]) == [
        (0, 15), (20, 31)]
    evs = [_dev("a(1)", 0, 10), _dev("b(2)", 5, 10), _dev("a(1)", 20, 10)]
    assert tracing.busy_ns(evs) == 25
    assert tracing.top_programs(evs) == [["a", 20e-9], ["b", 10e-9]]
    assert tracing.program_seconds(evs, lambda n: n.startswith("a")) == 20e-9


def test_clip_to_window_and_idle_gaps_named_by_innermost_span():
    rows = [_host(tracing.WINDOW, 100, 100), _dev("x(1)", 90, 30),
            _dev("y(1)", 150, 10), _dev("z(1)", 300, 5),
            _host("iteration", 100, 100), _host("fit", 160, 30)]
    lo, hi = tracing.window_bounds(rows)
    assert (lo, hi) == (100, 200)
    dev = tracing.device_rows(rows, lo, hi)["/device:TPU:0"]
    assert [(e["start"], e["dur"]) for e in dev] == [(100, 20), (150, 10)]
    gaps = tracing.idle_gaps(dev, rows, lo, hi)
    assert gaps == [["fit", 40e-9], ["iteration", 30e-9]]


def test_self_time_and_span_union():
    spans = [{"name": "map_wave", "ts": 0, "dur": 100, "tid": 1},
             {"name": "map_many", "ts": 10, "dur": 80, "tid": 1},
             {"name": "batch_cost", "ts": 20, "dur": 30, "tid": 1},
             {"name": "schedule", "ts": 40, "dur": 20, "tid": 1},
             {"name": "fit", "ts": 120, "dur": 10, "tid": 1}]
    got = tracing.self_time_s(spans, {"map_wave", "map_many"},
                              {"batch_cost", "schedule"})
    assert abs(got - 60e-6) < 1e-15
    assert abs(tracing.span_union_s(spans, {"map_many", "fit"})
               - 90e-6) < 1e-15


def test_recorded_trace_busy_matches_a_timeline():
    rows = json.loads(FIXTURE.read_text())
    dev = [r for r in rows if r["plane"].startswith(tracing.DEVICE_PREFIX)]
    assert dev
    lo = min(r["start"] for r in dev)
    hi = max(r["start"] + r["dur"] for r in dev)
    # independent count: +1/-1 at every boundary, busy where the sum > 0
    edges = sorted([(r["start"], 1) for r in dev]
                   + [(r["start"] + r["dur"], -1) for r in dev],
                   key=lambda e: (e[0], -e[1]))
    depth, busy, last = 0, 0, lo
    for t, d in edges:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    assert tracing.busy_ns(dev) == busy
    gaps = tracing.idle_gaps(dev, [], lo, hi, n=10 ** 9)
    assert abs(sum(g for _, g in gaps) * 1e9 + busy - (hi - lo)) < 1e-3
    total = sum(v for _, v in tracing.top_programs(dev, n=10 ** 9))
    assert abs(total - sum(r["dur"] for r in dev) / 1e9) < 1e-12


def test_readers_on_the_recorded_trace():
    rows = json.loads(FIXTURE.read_text())
    dev = [r for r in rows if r["plane"].startswith(tracing.DEVICE_PREFIX)]
    lo = min(r["start"] for r in dev)
    hi = max(r["start"] + r["dur"] for r in dev)
    ctx = {"spans": [], "device": tracing.device_rows(rows, lo, hi),
           "window_s": (hi - lo) / 1e9, "trace_window_s": (hi - lo) / 1e9,
           "iterations": 4, "evaluations": 3, "compiles_in_window": 0}
    idle = spec.load_reader("device.idle_share")(ctx)
    assert 0.0 <= idle < 100.0
    busy = tracing.busy_ns(dev) / 1e9
    assert np.isclose(idle, 100 * (1 - busy / ((hi - lo) / 1e9)))
    cost = spec.load_reader("costing.device_ms_per_eval")(ctx)
    n_cost = sum(r["dur"] for r in dev if "_batch_cost" in r["name"])
    assert (cost is None) == (n_cost == 0)
    if cost is not None:
        assert np.isclose(cost, 1e3 * n_cost / 1e9 / 3)
