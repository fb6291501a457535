"""Cells, configurations, traffic, limits and metric readers found by name."""

import json

import spec
import verify

BENCH = spec.load_benchmark()


def test_every_workload_resolves():
    for w in BENCH["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert set(cell.limits) == set(verify.NUMBERS)
        assert {"strategy", "n_sample", "propose_k", "evaluate_all_legal",
                "pipeline", "batch_prefill", "warm_until",
                "check_sample"} <= set(cell.traffic)
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer


def test_config_files_declare_their_cuts():
    for c in BENCH["configs"]:
        cfg = json.loads((spec.ROOT / c["file"]).read_text())
        assert c["file"].startswith("bench/")
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
        defaults = cfg["mapper_defaults"]
        for key in c["reduced"]:
            assert cfg[key] != defaults[key]


def test_every_metric_has_a_reader_that_reads_nothing_from_nothing():
    empty = {"spans": [], "device": {}, "window_s": 1.0,
             "trace_window_s": 0.0, "iterations": 0, "evaluations": 0,
             "compiles_in_window": 0}
    names = {p.stem for p in (spec.BENCH / "metrics").glob("*.py")}
    assert {m["name"] for m in BENCH["per_layer"]} <= names
    for name in names:
        got = spec.load_reader(name)(empty)
        assert got is None or got == 0, (name, got)


def test_unknown_workload_is_refused():
    import pytest
    with pytest.raises(SystemExit):
        spec.load_cell("no_such.cell")
