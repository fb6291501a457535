"""A whole run on the CPU at a small size: clean, the control, and faults.

The run skips only the look for a chip; everything else is the harness's
own path.  A clean run is correct; the lower-precision reference put in
the program's place is not; and neither is a run whose timed path is
broken underneath, once for each fault an evaluation can carry.
"""

import json

import pytest

import control
import run
import spec
from test_configs import rows

TRAFFIC = {"strategy": "random", "pool_seed": 7, "n_sample": 64,
           "propose_k": 8,
           "evaluate_all_legal": True, "pipeline": False,
           "batch_prefill": True,
           "warm_until": {"filter_observations": 8, "cost_observations": 3},
           "max_observations": 64, "warm_point": [4, 8, 128, 8, 16, 144, 32],
           "check_sample": 50,
           "max_warm_iterations": 50}


def small_cell() -> spec.Cell:
    from repro.core.workloads import googlenet
    config = json.loads((spec.BENCH / "configs" / "googlenet.json")
                        .read_text())
    config["graph"] = {"name": "googlenet",
                       "layers": rows(googlenet(1, scale=4))}
    limits = json.loads((spec.BENCH / "limits" / "default.json").read_text())
    bench = spec.load_benchmark()
    return spec.Cell("googlenet.small", 1, config, dict(TRAFFIC), limits,
                     bench["end_to_end"], bench["per_layer"])


def go(tmp_path, seed=2**31 + 11):
    dev = run.device_info(1, require_tpu=False)
    return run.run_cell(small_cell(), seed, 0.1, False, dev,
                        out_dir=tmp_path, warm_up=lambda *a: None)


@pytest.fixture(scope="module")
def clean(tmp_path_factory):
    return go(tmp_path_factory.mktemp("clean"))


def test_clean_run_is_correct(clean):
    assert clean["correct"], clean["check"]
    assert clean["info"]["checked"] >= 1
    assert clean["metrics"]["evals_per_s"]["value"] > 0


def test_control_is_not_correct(clean):
    got = control.readings(small_cell(), clean)
    assert not got["control_correct"], got


def _costing_altered(mp):
    from repro.engine import overlap
    orig = overlap.PendingPairedCost.latency_row
    mp.setattr(overlap.PendingPairedCost, "latency_row",
               lambda self: orig(self) * (1 + 1e-6))


def _half_batch(mp):
    from repro.core import mapper
    orig = mapper.PimMapper.map_many_phases

    def phases(self, graph, cfgs, **kw):
        half = list(cfgs)[:max(1, len(cfgs) // 2)]
        inner = orig(self, graph, half, **kw)

        def gen():
            res = yield from inner
            return res + [res[-1]] * (len(cfgs) - len(half))
        return gen()
    mp.setattr(mapper.PimMapper, "map_many_phases", phases)


def _schedule_altered(mp):
    from repro.engine import scheduler_opt
    orig = scheduler_opt._finish

    def finish(*a, **kw):
        res = orig(*a, **kw)
        res.latency_s *= 1 + 1e-6
        return res
    mp.setattr(scheduler_opt, "_finish", finish)


@pytest.mark.parametrize("fault", [_costing_altered, _half_batch,
                                   _schedule_altered])
def test_broken_timed_path_is_not_correct(fault, tmp_path, monkeypatch):
    from repro.core.mapper import clear_mapper_caches
    clear_mapper_caches()
    fault(monkeypatch)
    res = go(tmp_path, seed=2**31 + 12)
    clear_mapper_caches()
    assert not res["correct"], res["check"]
