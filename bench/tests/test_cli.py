"""The command line: no TPU means no result, and the last line's schema."""

import json
import os
import shutil
import subprocess
import sys

import run
import spec


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "googlenet.random_all_legal", "--seed", "3000000000",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(spec.ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_bare_benchmark_directory_exits_nonzero(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_last_line_schema(capsys):
    result = {"correct": True, "attempted": 3, "failed": 0,
              "metrics": {"evals_per_s": {"value": 0.5, "unit": "evals/s"}},
              "device": {"platform": "tpu", "kind": "TPU v5 lite",
                         "count": 1, "memory_peak_bytes": 1},
              "info": {}, "check": {"area_rel": {"value": 0.0,
                                                 "limit": 1e-12}},
              "_lines": ["area_rel 0.0 limit 1e-12 ok"]}
    run.emit(result)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "check"
    assert err.strip().splitlines()[-1] == "area_rel 0.0 limit 1e-12 ok"
