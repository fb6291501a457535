#!/usr/bin/env python3
"""Readings for the limits: the program and its control, seed by seed.

    python bench/control.py --workload googlenet.random_all_legal \\
        --seeds 11,12,13 --seconds 20

For each seed, in this one process: a run of the cell as ``bench/run.py``
makes it (shorter window), the numbers its check compares (the program's
readings), and the same numbers with the reference computed in float32 put
in the program's place (the control's readings).  One JSON line per seed;
the limits in ``bench/limits`` sit between the largest program reading and
the smallest control reading.  Needs the chip, like ``bench/run.py``.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run
import spec
import verify


def readings(cell, result) -> dict:
    low = verify.Reference(cell.config, np.float32)
    sched = result["_capture"].schedules
    observed = verify.control_observed(low, result["_sample"], sched)
    areas = verify.control_areas(cell.config, result["_areas"], np.float32)
    ctl = verify.compare(verify.Reference(cell.config), observed, areas,
                         sched)
    prog = {k: v["value"] for k, v in result["check"].items()}
    return {"program": prog,
            "control": {k: run._num(ctl[k]) for k in verify.NUMBERS},
            "control_correct": verify.judge(ctl, cell.limits)[0]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from repro import runtime
    from repro.core.mapper import clear_mapper_caches
    cell = spec.load_cell(args.workload)
    runtime.configure_compile_cache(run.ROOT)
    device = run.device_info(cell.chips)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        clear_mapper_caches()
        # this process holds every program after the first seed's warm-up
        res = run.run_cell(cell, seed, args.seconds, False, device,
                           warm_up=run.warm_all if i == 0
                           else (lambda *a: None))
        line = {"seed": seed, "correct": res["correct"],
                "evaluations": res["attempted"],
                "checked": res["info"]["checked"],
                "choices_differing": res["info"]["choices_differing"],
                **readings(cell, res)}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
