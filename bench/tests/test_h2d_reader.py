"""``costing.h2d_kb_per_eval``: the bytes paired costing hands the device."""

import spec


def _ctx(spans, evaluations=4):
    return {"spans": spans, "device": {}, "window_s": 1.0,
            "trace_window_s": 1.0, "iterations": 1,
            "evaluations": evaluations, "compiles_in_window": 0}


def _dispatch(ts, **args):
    return {"name": "dispatch_paired", "ts": ts, "dur": 1.0, "tid": 1,
            "args": args}


def test_sums_the_bytes_of_every_paired_dispatch_per_evaluation():
    read = spec.load_reader("costing.h2d_kb_per_eval")
    ctx = _ctx([_dispatch(0, pairs=300, bytes=120_320),
                _dispatch(5, pairs=90, bytes=30_080)])
    assert read(ctx) == (120_320 + 30_080) / 1e3 / 4


def test_reads_nothing_where_the_spans_carry_no_bytes():
    read = spec.load_reader("costing.h2d_kb_per_eval")
    assert read(_ctx([_dispatch(0, pairs=300)])) is None
    assert read(_ctx([], evaluations=0)) is None
