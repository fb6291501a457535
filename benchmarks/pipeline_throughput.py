"""End-to-end DSE-iteration throughput: device-resident pipeline vs staged.

Measures the PR 7 contract: a COLD scan-backend DSE campaign (the shape a
fresh tuning process actually runs) through ``run_dse(pipeline=True)`` —
the fused propose chain, deferred fits, cross-config scheduler prefill and
canonical bucket shapes — against the PR 6 staged path (per-stage host
round-trips, exact pow2 scheduler shapes, per-mapping prefill).

Framing
-------
Each side runs in its OWN subprocess (jit caches must not leak between
them).  A subprocess first runs the same campaign with
``scheduler_backend="loop"`` untimed: that warms every mapper / tuner /
batch-cost program while touching no scan-scheduler program, so the timed
phase isolates what the pipeline actually changes — scheduler program
count and per-iteration host synchronization — rather than re-measuring
the shared mapping work's first-compile storm.  Mapper memos are cleared
between phases; both sides then run the identical campaign cold on the
scan backend.

Contracts (asserted here, gated in CI via ``benchmarks.bench_gate`` on
``experiments/BENCH_7.json``):

* the fused and staged observation streams are IDENTICAL (the speedup is
  parity-pinned, not bought with different search results);
* fused / staged >= 2x end-to-end (``--smoke`` softens to 1.2x: CI workers
  are loaded and the smoke campaign is short);
* the fused run actually took the fused path (``fused_propose`` trace
  spans were recorded);
* the 16x16 / 960-link Fig. 12 array — the scheduler's memory-bound worst
  case — solves at >= 1x the loop reference on CPU (the
  ``scheduler_16x16_vs_loop`` gate; the Pallas streaming kernel targets
  TPU, the jnp dense path must at least break even on CPU).
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

BENCH_ID = 7
BENCH_SCHEMA = "nicepim-bench/1"

MAPPER_KW = dict(max_optim_iter=1, lm_cap=40, n_wr=3)


# ---------------------------------------------------------------------------
# worker: one cold campaign in a fresh process
# ---------------------------------------------------------------------------


def worker(mode: str, iterations: int, n_sample: int) -> None:
    from repro.core.dse import WorkloadEvaluator, run_dse
    from repro.core.mapper import _sharing_latency, clear_mapper_caches
    from repro.core.tuner import PimTuner
    from repro.core.workloads import googlenet
    from repro.obs.trace import Tracer
    import repro.engine.scheduler_opt as so

    nets = [googlenet(1, scale=8)]
    pipeline = mode == "fused"

    def campaign(backend: str, tracer=None):
        ev = WorkloadEvaluator(nets, mapper_kwargs=MAPPER_KW,
                               scheduler_backend=backend)
        return run_dse(PimTuner(seed=0, n_sample=n_sample, backend="scan"),
                       ev, iterations=iterations, propose_k=8,
                       pipeline=pipeline, tracer=tracer)

    # phase 1 (untimed): warm the shared mapper/tuner/batch-cost programs
    # without compiling any scan-scheduler program
    campaign("loop")
    clear_mapper_caches()
    _sharing_latency.cache_clear()

    if mode == "staged":
        so._PAD_SHAPES = False        # the PR 6 exact-shape baseline
    tracer = Tracer()
    t0 = time.perf_counter()
    res = campaign("scan", tracer=tracer)
    dt = time.perf_counter() - t0

    stream = [(o.iteration, o.cfg.as_tuple(), o.area_mm2, o.legal, o.cost)
              for o in res.observations]
    fused_spans = sum(1 for ev in tracer.events()
                      if ev.get("name") == "fused_propose")
    print(json.dumps({
        "mode": mode, "secs": dt, "iterations": iterations,
        "sched_programs": so._scan_solve._cache_size(),
        "fused_spans": fused_spans, "stream": stream,
    }), flush=True)


def _run_worker(mode: str, iterations: int, n_sample: int) -> dict:
    cmd = [sys.executable, "-m", "benchmarks.pipeline_throughput",
           "--worker", mode, "--iters", str(iterations),
           "--n-sample", str(n_sample)]
    proc = subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT)
    if proc.returncode != 0:
        raise RuntimeError(f"{mode} worker failed:\n{proc.stderr[-4000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# the 16x16 memory-bound scheduler case (scheduler_16x16_vs_loop gate)
# ---------------------------------------------------------------------------


def _single_16x16(iters: int, seed: int = 0) -> dict:
    from benchmarks.scheduler_throughput import CHUNK, EPJ, FLIT_BW, FREQ, \
        fig12_problem
    from repro.core.scheduler import solve_ilp_ls
    from repro.runtime import native_kernels

    noc, sets = fig12_problem(16, 4)
    chunks = [CHUNK] * len(sets)
    kw = dict(seed=seed, restarts=6, iters=iters)
    solve_ilp_ls(noc, sets, chunks, FLIT_BW, FREQ, EPJ,
                 backend="scan", **kw)                 # compile, untimed
    t0 = time.perf_counter()
    scan = solve_ilp_ls(noc, sets, chunks, FLIT_BW, FREQ, EPJ,
                        backend="scan", **kw)
    t_scan = time.perf_counter() - t0
    t0 = time.perf_counter()
    loop = solve_ilp_ls(noc, sets, chunks, FLIT_BW, FREQ, EPJ,
                        backend="loop", **kw)
    t_loop = time.perf_counter() - t0
    assert scan.max_link_bytes <= loop.max_link_bytes + 1e-9
    return {
        "table": "pipeline", "case": "single_16x16",
        "path": "pallas-stream" if native_kernels() else "jnp-dense",
        "scan_s": t_scan, "loop_s": t_loop, "speedup": t_loop / t_scan,
    }


# ---------------------------------------------------------------------------
# orchestration
# ---------------------------------------------------------------------------


def run(iterations: int = 6, n_sample: int = 256,
        min_speedup: float = 2.0, sched_iters: int = 1200) -> list[dict]:
    fused = _run_worker("fused", iterations, n_sample)
    staged = _run_worker("staged", iterations, n_sample)

    assert fused["stream"] == staged["stream"], (
        "pipeline and staged DSE observation streams diverged — the "
        "speedup would not be parity-pinned")
    assert fused["fused_spans"] >= iterations, (
        f"only {fused['fused_spans']} fused_propose spans for "
        f"{iterations} iterations — the fused path was not taken")
    assert staged["fused_spans"] == 0, "staged run took the fused path"

    speedup = staged["secs"] / fused["secs"]
    rows = [{
        "table": "pipeline", "case": "dse_campaign",
        "iterations": iterations, "n_sample": n_sample,
        "fused_s": fused["secs"], "staged_s": staged["secs"],
        "iters_per_s_fused": iterations / fused["secs"],
        "iters_per_s_staged": iterations / staged["secs"],
        "fused_programs": fused["sched_programs"],
        "staged_programs": staged["sched_programs"],
        "speedup": speedup, "min_speedup": min_speedup,
        "parity": "match",
    }]
    assert speedup >= min_speedup, (
        f"device-resident pipeline only {speedup:.2f}x over the staged "
        f"path (contract: >={min_speedup}x)")

    single = _single_16x16(sched_iters)
    assert single["speedup"] >= 1.0, (
        f"16x16 scheduler case {single['speedup']:.2f}x vs loop — the "
        f"memory-bound case regressed below break-even")
    rows.append(single)
    return rows


SMOKE_KW = dict(iterations=4, n_sample=128, min_speedup=1.2,
                sched_iters=400)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="short campaign + soft thresholds (CI)")
    ap.add_argument("--worker", default=None, help="internal: run one side")
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--n-sample", type=int, default=None)
    ap.add_argument("--out", default=None, metavar="BENCH_7.json",
                    help="write the perf artifact here (default "
                         "experiments/BENCH_7.json)")
    args = ap.parse_args()

    if args.worker:
        worker(args.worker, args.iters, args.n_sample)
        return

    kw = dict(SMOKE_KW) if args.smoke else {}
    if args.iters is not None:
        kw["iterations"] = args.iters
    if args.n_sample is not None:
        kw["n_sample"] = args.n_sample
    t0 = time.time()
    rows = run(**kw)
    total_s = time.time() - t0

    r = rows[0]
    print(f"pipeline_staged,{1e6 * r['staged_s'] / r['iterations']:.0f},"
          f"iters_per_s={r['iters_per_s_staged']:.3f} "
          f"programs={r['staged_programs']}")
    print(f"pipeline_fused,{1e6 * r['fused_s'] / r['iterations']:.0f},"
          f"iters_per_s={r['iters_per_s_fused']:.3f} "
          f"programs={r['fused_programs']} "
          f"speedup={r['speedup']:.2f}x parity={r['parity']}")
    s = rows[1]
    print(f"pipeline_single_16x16,{s['scan_s'] * 1e6:.0f},"
          f"path={s['path']} speedup={s['speedup']:.2f}x")

    tol = 0.40 if args.smoke else 0.25
    bench = {
        "schema": BENCH_SCHEMA,
        "bench_id": BENCH_ID,
        "mode": "smoke" if args.smoke else "full",
        "generated_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "sections_s": {"pipeline": total_s},
        "benchmarks": [
            {"name": "pipeline_fused",
             "us_per_call": 1e6 * r["fused_s"] / r["iterations"],
             "derived": f"speedup={r['speedup']:.2f}x"},
            {"name": "pipeline_single_16x16",
             "us_per_call": s["scan_s"] * 1e6,
             "derived": f"speedup={s['speedup']:.2f}x path={s['path']}"},
        ],
        "gates": {
            "pipeline_speedup": {"value": float(r["speedup"]),
                                 "tolerance": tol,
                                 "higher_is_better": True},
            "scheduler_16x16_vs_loop": {"value": float(s["speedup"]),
                                        "tolerance": tol,
                                        "higher_is_better": True},
        },
    }
    out = Path(args.out) if args.out else (
        ROOT / "experiments" / f"BENCH_{BENCH_ID}.json")
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(bench, indent=1) + "\n")
    print(f"# wrote {out}", flush=True)


if __name__ == "__main__":
    main()
