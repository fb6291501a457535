"""Innermost-span attribution and the per-phase readers built on it."""

import pytest

import attribution
import spec

PHASES = ("mapper.cand_ms_per_eval", "mapper.dp_ms_per_eval",
          "mapper.dl_ms_per_eval", "costing.host_ms_per_eval",
          "device.wait_ms_per_eval", "scheduler.host_ms_per_eval",
          "evaluator.accounting_ms_per_eval")
TUNER = ("propose", "fit")


def _s(name, ts, end, tid=1, **args):
    return {"name": name, "ts": ts, "dur": end - ts, "tid": tid,
            "args": args}


# one iteration of the campaign loop, in microseconds; a second thread's
# span and a child that overruns its parent by rounding
SPANS = [
    _s("iteration", 0, 100), _s("propose", 2, 5),
    _s("evaluate", 10, 90), _s("map_wave", 12, 60),
    _s("cand_dispatch", 14, 20, keys=40, built=30),
    _s("dispatch_paired", 16, 19, pairs=300),
    _s("cand_build", 22, 30, tables=30),
    _s("device_wait", 24, 28, what="batch_cost"),
    _s("dp_solve", 31, 40, segments=9),
    _s("overlap_drain", 62, 88), _s("accounting", 64, 70),
    _s("sched_problems", 65, 66),
    _s("schedule", 72, 80, problems=5),
    _s("device_wait", 75, 80.0000001, what="scan_solve"),
    _s("fit", 92, 96),
    _s("cand_dispatch", 0, 100, tid=2, keys=1, built=1),
]
# innermost microseconds, by hand
EXPECT = {"iteration": 13, "propose": 3, "evaluate": 6, "map_wave": 25,
          "cand_dispatch": 3, "dispatch_paired": 3, "cand_build": 4,
          "device_wait": 9, "dp_solve": 9, "overlap_drain": 12,
          "accounting": 5, "sched_problems": 1, "schedule": 3, "fit": 4}
WINDOW_US = 120
EVALS = 2


def _ctx(spans=SPANS):
    return {"spans": spans, "device": {}, "window_s": 1.0,
            "trace_window_s": WINDOW_US / 1e6, "iterations": 1,
            "evaluations": EVALS, "compiles_in_window": 0}


def test_innermost_attribution_on_nested_spans():
    got = attribution.innermost_s(SPANS)
    assert set(got) == set(EXPECT)
    for name, us in EXPECT.items():
        assert got[name] == pytest.approx(us / 1e6, abs=1e-12), name
    # a partition of the loop thread's covered time
    assert sum(got.values()) == pytest.approx(
        attribution.covered_s(SPANS), rel=1e-12)
    assert attribution.loop_thread(SPANS) == 1
    assert attribution.innermost_s([]) == {}


def test_phase_readers_on_a_synthetic_ctx():
    ctx = _ctx()
    want = {"mapper.cand_ms_per_eval": 3 + 4, "mapper.dp_ms_per_eval": 9,
            "mapper.dl_ms_per_eval": None,
            "costing.host_ms_per_eval": 3, "device.wait_ms_per_eval": 9,
            "scheduler.host_ms_per_eval": 1 + 3,
            "evaluator.accounting_ms_per_eval": 5,
            "trace.unattributed_ms_per_eval":
                13 + 6 + 25 + 12 + (WINDOW_US - 100)}
    for name, us in want.items():
        got = spec.load_reader(name)(ctx)
        if us is None:
            assert got is None, name
        else:
            assert got == pytest.approx(1e-3 * us / EVALS, rel=1e-9), name
    assert spec.load_reader("costing.pairs_per_eval")(ctx) == 300 / EVALS
    assert spec.load_reader("scheduler.searched_per_eval")(ctx) == 5 / EVALS


def test_phases_tuner_and_unattributed_sum_to_the_traced_wall():
    ctx = _ctx()
    phases = [spec.load_reader(n)(ctx) for n in PHASES]
    inner = attribution.innermost_s(SPANS)
    tuner = 1e3 * sum(inner[n] for n in TUNER) / EVALS
    rest = spec.load_reader("trace.unattributed_ms_per_eval")(ctx)
    total = sum(p or 0.0 for p in phases) + tuner + rest
    wall = ctx["trace_window_s"] * 1e3 / EVALS
    assert total == pytest.approx(wall, rel=1e-9)


def test_readers_leave_out_what_an_older_program_has_no_span_for():
    old = [s for s in SPANS if s["name"] in
           {"iteration", "propose", "evaluate", "map_wave", "overlap_drain",
            "dispatch_paired", "schedule", "fit"}]
    ctx = _ctx(old)
    for name in ("mapper.cand_ms_per_eval", "mapper.dp_ms_per_eval",
                 "mapper.dl_ms_per_eval", "device.wait_ms_per_eval",
                 "evaluator.accounting_ms_per_eval"):
        assert spec.load_reader(name)(ctx) is None, name
    for name in ("costing.host_ms_per_eval", "scheduler.host_ms_per_eval",
                 "trace.unattributed_ms_per_eval", "costing.pairs_per_eval",
                 "scheduler.searched_per_eval"):
        assert spec.load_reader(name)(ctx) > 0, name


def test_traced_run_prints_every_span_metric(tmp_path):
    from repro.core.mapper import clear_mapper_caches
    from test_run import small_cell
    import run
    clear_mapper_caches()   # cold memos: every phase has work to do
    dev = run.device_info(1, require_tpu=False)
    res = run.run_cell(small_cell(), 2**31 + 13, 0.1, True, dev,
                       out_dir=tmp_path, warm_up=lambda *a: None)
    clear_mapper_caches()
    assert res["correct"], res["check"]
    names = [m["name"] for m in spec.load_benchmark()["per_layer"]
             if m["source"] == "program_span"]
    assert names
    for name in names:
        assert res["metrics"][name]["value"] > 0, name
