"""Mega-campaign throughput: sharded multi-tenant service vs single-stream.

Measures the PR 9 contract on the workload ROADMAP item 1 describes: a DSE
service receiving MANY tenant campaign submissions — several (workloads,
seed) streams, each submitted repeatedly (nightly re-runs, multiple users
sweeping the same design point).  Two ways to run the identical submission
list:

* **single-stream** (the PR 7 path): each submission runs
  ``run_dse(pipeline=True)`` sequentially with a fresh evaluator and no
  shared state — the only option before this PR;
* **sharded** (:class:`repro.engine.sharded.ShardedCampaign`): all
  submissions as tenants of one campaign on a >=4-device ``config`` mesh
  (candidate rows sharded via NamedSharding, per-wave shard_map stats),
  async wave overlap across tenants, and ONE shared
  :class:`PersistentEvalCache` — repeated submissions dedupe their
  mapper/scheduler work against the durable content-addressed table while
  still emitting their full observation streams.

Both sides run in their own subprocess (jit caches must not leak) with
``--xla_force_host_platform_device_count=4`` so the mesh exists even on a
single-CPU host; each warms shared programs untimed on a throwaway seed
first.  Contracts asserted here and gated in CI via
``benchmarks.bench_gate`` on ``experiments/BENCH_9.json``:

* the sharded service and the single-stream baseline produce IDENTICAL
  per-submission observation streams (hence identical multisets) and an
  identical Pareto front — the speedup is parity-pinned;
* sharded >= 2x end-to-end over single-stream (``--smoke`` softens to
  1.2x: short campaigns amortize less);
* kill-and-resume: a worker process is killed mid-campaign (``os._exit``
  after N ingested waves, no shutdown path runs) and the resumed run
  completes the exact reference stream with ZERO re-evaluations of
  already-cached points (``reeval_preexisting == 0`` — every pre-kill
  evaluation survived in sqlite and was served, not re-mapped).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

BENCH_ID = 9
BENCH_SCHEMA = "nicepim-bench/1"
N_DEVICES = 4

MAPPER_KW = dict(max_optim_iter=1, lm_cap=20, n_wr=2)
SEEDS = (11, 12)          # distinct tenants


def _specs(seeds, repeats: int, iterations: int, propose_k: int,
           n_sample: int):
    from repro.core.workloads import googlenet
    from repro.engine import TenantSpec
    nets = [googlenet(1, scale=8)]
    return [TenantSpec(name=f"t{seed}r{rep}", workloads=nets, seed=seed,
                       iterations=iterations, propose_k=propose_k,
                       n_sample=n_sample, evaluate_all_legal=True,
                       evaluator_kwargs=dict(mapper_kwargs=MAPPER_KW))
            for seed in seeds for rep in range(repeats)]


def _stream(observations):
    return [[o.iteration, list(o.cfg.as_tuple()), o.area_mm2, o.legal,
             o.cost] for o in observations]


def _pareto_points(front):
    return sorted((p.latency_s, p.energy_pj, p.area_mm2)
                  for p in front.points)


def _warm(iterations: int, propose_k: int, n_sample: int) -> None:
    """Untimed: run each UNIQUE tenant stream once, with no cache.

    One-time XLA compiles depend on the configs a stream actually proposes
    (bucket shapes), so warming a throwaway seed leaves the timed phase
    dominated by compile cost that the process-wide jit cache dedupes
    identically on BOTH sides.  Instead each worker warms the real unique
    streams — every jitted program the timed phase needs is compiled — and
    then drops the mapper memos.  Crucially NO persistent/eval cache is
    attached here: the timed sharded campaign starts with a cold table and
    earns its dedup from the campaign machinery alone.
    """
    from repro.core.dse import WorkloadEvaluator, run_dse
    from repro.core.mapper import _sharing_latency, clear_mapper_caches
    from repro.core.surrogates import make_strategy
    for spec in _specs(SEEDS, 1, iterations, propose_k, n_sample):
        ev = WorkloadEvaluator(list(spec.workloads), mapper_kwargs=MAPPER_KW,
                               clear_caches_between_configs=True,
                               batch_prefill=True)
        run_dse(make_strategy("nicepim", cons=spec.cons, seed=spec.seed,
                              n_sample=n_sample),
                ev, iterations=iterations, propose_k=propose_k,
                evaluate_all_legal=True, pipeline=True)
    clear_mapper_caches()
    _sharing_latency.cache_clear()


def _require_cpu_mesh() -> None:
    """Exit non-zero unless the forced virtual CPU devices are here.

    ``--xla_force_host_platform_device_count`` applies to the CPU backend
    only; on a TPU host the workers would see the chip instead, so this
    CPU rehearsal refuses to run there.
    """
    import jax
    devs = jax.devices()
    if devs[0].platform != "cpu" or len(devs) < N_DEVICES:
        raise SystemExit(
            f"campaign_throughput needs {N_DEVICES} forced CPU devices; "
            f"found {len(devs)} {devs[0].platform} device(s).  On a TPU "
            f"host, `python chip_smoke.py --chips 4` runs the sharded "
            f"campaign on the chips")


# ---------------------------------------------------------------------------
# workers (one subprocess each; --xla_force_host_platform_device_count set
# by the orchestrator before jax ever imports)
# ---------------------------------------------------------------------------


def worker_single(repeats, iterations, propose_k, n_sample) -> None:
    _require_cpu_mesh()
    from repro.core.dse import WorkloadEvaluator, run_dse
    from repro.core.surrogates import make_strategy
    from repro.engine.pareto import ParetoFront

    _warm(iterations, propose_k, n_sample)
    specs = _specs(SEEDS, repeats, iterations, propose_k, n_sample)
    pareto = ParetoFront()
    streams = {}
    t0 = time.perf_counter()
    for spec in specs:
        strat = make_strategy("nicepim", cons=spec.cons, seed=spec.seed,
                              n_sample=spec.n_sample)
        ev = WorkloadEvaluator(list(spec.workloads),
                               mapper_kwargs=MAPPER_KW,
                               clear_caches_between_configs=True)
        res = run_dse(strat, ev, iterations=spec.iterations,
                      propose_k=spec.propose_k, pareto=pareto,
                      evaluate_all_legal=True, pipeline=True)
        streams[spec.name] = _stream(res.observations)
    dt = time.perf_counter() - t0
    print(json.dumps({"mode": "single", "secs": dt, "streams": streams,
                      "pareto": _pareto_points(pareto)}), flush=True)


def worker_sharded(repeats, iterations, propose_k, n_sample,
                   workdir: str) -> None:
    _require_cpu_mesh()
    from repro.engine import PersistentEvalCache, ShardedCampaign
    from repro.obs.trace import Tracer

    _warm(iterations, propose_k, n_sample)
    specs = _specs(SEEDS, repeats, iterations, propose_k, n_sample)
    cache = PersistentEvalCache(Path(workdir) / "evals.sqlite")
    tracer = Tracer()
    camp = ShardedCampaign(specs, cache=cache, queue_depth=4,
                           eval_workers=2,
                           checkpoint=Path(workdir) / "ckpt.json",
                           tracer=tracer)
    t0 = time.perf_counter()
    out = camp.run()
    dt = time.perf_counter() - t0
    spans = [ev.get("name") for ev in tracer.events()]
    print(json.dumps({
        "mode": "sharded", "secs": dt,
        "streams": {n: _stream(r.observations)
                    for n, r in out.results.items()},
        "pareto": _pareto_points(out.pareto),
        "cache": out.cache_stats,
        "evaluations": sum(s.evaluator.evaluations for s in camp._states),
        "propose_spans": spans.count("fused_propose"),
        "eval_spans": spans.count("wave_evaluate"),
    }), flush=True)


def worker_kill(iterations, propose_k, n_sample, workdir: str,
                die_after: int) -> None:
    """Run one tenant sharded, then die mid-campaign without cleanup."""
    _require_cpu_mesh()
    from repro.engine import PersistentEvalCache, ShardedCampaign

    class DyingCampaign(ShardedCampaign):
        waves = 0

        def _ingest_wave(self, st, wave, evaluated):
            super()._ingest_wave(st, wave, evaluated)
            DyingCampaign.waves += 1
            if DyingCampaign.waves >= die_after:
                # simulate SIGKILL: no finally blocks, no cache close, no
                # final checkpoint — only per-wave durability survives
                os._exit(42)

    _warm(iterations, propose_k, n_sample)
    specs = _specs(SEEDS[:1], 1, iterations, propose_k, n_sample)
    cache = PersistentEvalCache(Path(workdir) / "evals.sqlite")
    DyingCampaign(specs, cache=cache,
                  checkpoint=Path(workdir) / "ckpt.json").run()
    print(json.dumps({"mode": "kill", "survived": True}), flush=True)


def worker_resume(iterations, propose_k, n_sample, workdir: str) -> None:
    _require_cpu_mesh()
    from repro.engine import PersistentEvalCache, ShardedCampaign

    _warm(iterations, propose_k, n_sample)
    specs = _specs(SEEDS[:1], 1, iterations, propose_k, n_sample)
    cache = PersistentEvalCache(Path(workdir) / "evals.sqlite")
    camp = ShardedCampaign(specs, cache=cache,
                           checkpoint=Path(workdir) / "ckpt.json")
    out = camp.run()
    print(json.dumps({
        "mode": "resume", "resumed": out.resumed,
        "streams": {n: _stream(r.observations)
                    for n, r in out.results.items()},
        "cache": cache.stats,
        "evaluations": sum(s.evaluator.evaluations for s in camp._states),
    }), flush=True)


def _run_worker(mode: str, extra: list[str]) -> tuple[dict, int]:
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={N_DEVICES}"
            .strip())
    cmd = [sys.executable, "-m", "benchmarks.campaign_throughput",
           "--worker", mode] + extra
    proc = subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT,
                          env=env)
    if mode == "kill":
        if proc.returncode != 42:
            raise RuntimeError(
                f"kill worker should die with os._exit(42), got "
                f"{proc.returncode}:\n{proc.stderr[-4000:]}")
        return {}, proc.returncode
    if proc.returncode != 0:
        raise RuntimeError(f"{mode} worker failed:\n{proc.stderr[-4000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.returncode


# ---------------------------------------------------------------------------
# orchestration
# ---------------------------------------------------------------------------


def run(repeats: int = 4, iterations: int = 3, propose_k: int = 4,
        n_sample: int = 128, min_speedup: float = 2.0,
        die_after: int = 1, workdir: str | None = None) -> list[dict]:
    import tempfile
    base = Path(workdir) if workdir else Path(tempfile.mkdtemp(
        prefix="campaign_bench_"))
    (base / "sharded").mkdir(parents=True, exist_ok=True)
    (base / "faults").mkdir(parents=True, exist_ok=True)
    sizes = [str(repeats), str(iterations), str(propose_k), str(n_sample)]

    single, _ = _run_worker("single", sizes)
    sharded, _ = _run_worker("sharded", sizes + [str(base / "sharded")])

    # parity: identical per-submission streams => identical observation
    # multiset; identical Pareto front
    assert sharded["streams"] == single["streams"], (
        "sharded and single-stream observation streams diverged — the "
        "speedup would not be parity-pinned")
    assert sharded["pareto"] == single["pareto"], (
        "sharded and single-stream Pareto fronts diverged")
    assert sharded["propose_spans"] > 0 and sharded["eval_spans"] > 0, (
        "sharded run recorded no wave spans — the overlapped path was "
        "not taken")
    n_tenants = len(SEEDS) * repeats
    n_unique = len(SEEDS)
    # the structural contract: repeated submissions were deduped — the
    # mapper ran for the unique streams only
    assert sharded["evaluations"] <= single_evals_bound(
        sharded, n_unique, n_tenants), (
        f"sharded service re-evaluated duplicated submissions: "
        f"{sharded['evaluations']} mapper runs for {n_unique} unique "
        f"tenant streams")

    speedup = single["secs"] / sharded["secs"]
    rows = [{
        "table": "campaign", "case": "mega_campaign",
        "tenants": n_tenants, "unique": n_unique, "repeats": repeats,
        "iterations": iterations, "propose_k": propose_k,
        "n_sample": n_sample, "devices": N_DEVICES,
        "single_s": single["secs"], "sharded_s": sharded["secs"],
        "subs_per_s_single": n_tenants / single["secs"],
        "subs_per_s_sharded": n_tenants / sharded["secs"],
        "evaluations": sharded["evaluations"],
        "cache": sharded["cache"],
        "speedup": speedup, "min_speedup": min_speedup,
        "parity": "match",
    }]
    assert speedup >= min_speedup, (
        f"sharded mega-campaign only {speedup:.2f}x over the "
        f"single-stream path (contract: >={min_speedup}x)")

    # -- kill-and-resume ---------------------------------------------------
    _run_worker("kill", sizes + [str(base / "faults"), str(die_after)])
    resume, _ = _run_worker("resume", sizes + [str(base / "faults")])
    ref_name = f"t{SEEDS[0]}r0"
    assert resume["resumed"] == [ref_name], (
        f"resume did not pick up the killed tenant: {resume['resumed']}")
    assert resume["streams"][ref_name] == single["streams"][ref_name], (
        "resumed stream diverged from the uninterrupted reference")
    assert resume["cache"]["reeval_preexisting"] == 0, (
        f"resume re-evaluated {resume['cache']['reeval_preexisting']} "
        f"already-cached points — pre-kill evaluations were lost")
    rows.append({
        "table": "campaign", "case": "kill_and_resume",
        "die_after_waves": die_after,
        "resume_evaluations": resume["evaluations"],
        "reeval_preexisting": resume["cache"]["reeval_preexisting"],
        "preexisting": resume["cache"]["preexisting"],
    })
    return rows


def single_evals_bound(sharded: dict, n_unique: int, n_tenants: int) -> int:
    """Upper bound on legitimate mapper runs for the deduped service.

    Unique streams evaluate; repeats must be served from the shared cache.
    The bound is per-unique-stream work times the unique count (cache
    entries measure exactly that).
    """
    return sharded["cache"]["entries"]


SMOKE_KW = dict(repeats=3, iterations=2, propose_k=3, n_sample=64,
                min_speedup=1.2)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="short campaigns + soft thresholds (CI)")
    ap.add_argument("--worker", default=None,
                    help="internal: single|sharded|kill|resume")
    ap.add_argument("sizes", nargs="*", default=[])
    ap.add_argument("--out", default=None, metavar="BENCH_9.json",
                    help="write the perf artifact here (default "
                         "experiments/BENCH_9.json)")
    args = ap.parse_args()

    if args.worker:
        s = args.sizes
        if args.worker == "single":
            worker_single(int(s[0]), int(s[1]), int(s[2]), int(s[3]))
        elif args.worker == "sharded":
            worker_sharded(int(s[0]), int(s[1]), int(s[2]), int(s[3]), s[4])
        elif args.worker == "kill":
            worker_kill(int(s[1]), int(s[2]), int(s[3]), s[4], int(s[5]))
        elif args.worker == "resume":
            worker_resume(int(s[1]), int(s[2]), int(s[3]), s[4])
        else:
            raise SystemExit(f"unknown worker {args.worker!r}")
        return

    kw = dict(SMOKE_KW) if args.smoke else {}
    t0 = time.time()
    rows = run(**kw)
    total_s = time.time() - t0

    r = rows[0]
    print(f"campaign_single,{1e6 * r['single_s'] / r['tenants']:.0f},"
          f"subs_per_s={r['subs_per_s_single']:.3f}")
    print(f"campaign_sharded,{1e6 * r['sharded_s'] / r['tenants']:.0f},"
          f"subs_per_s={r['subs_per_s_sharded']:.3f} "
          f"speedup={r['speedup']:.2f}x parity={r['parity']} "
          f"evals={r['evaluations']}")
    k = rows[1]
    print(f"campaign_kill_resume,reeval={k['reeval_preexisting']},"
          f"resume_evals={k['resume_evaluations']} "
          f"preexisting={k['preexisting']}")

    tol = 0.40 if args.smoke else 0.25
    bench = {
        "schema": BENCH_SCHEMA,
        "bench_id": BENCH_ID,
        "mode": "smoke" if args.smoke else "full",
        "generated_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "sections_s": {"campaign": total_s},
        "benchmarks": [
            {"name": "campaign_sharded",
             "us_per_call": 1e6 * r["sharded_s"] / r["tenants"],
             "derived": f"speedup={r['speedup']:.2f}x "
                        f"tenants={r['tenants']} evals={r['evaluations']}"},
            {"name": "campaign_kill_resume",
             "us_per_call": 0.0,
             "derived": f"reeval={k['reeval_preexisting']} "
                        f"preexisting={k['preexisting']}"},
        ],
        "gates": {
            "campaign_sharded_speedup": {"value": float(r["speedup"]),
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
