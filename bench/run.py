#!/usr/bin/env python3
"""NicePIM DSE benchmark: design points evaluated per second on the chip.

    python bench/run.py --workload googlenet.random_all_legal --seed 7 \\
        --seconds 30 --trace 0

One process, on the machine that holds the chips: it names the device
(and exits non-zero, printing no result, without a TPU), builds the cell's
graph, design space and tuner from ``BENCHMARK.json`` and the cell's files
and from ``--seed``, warms every program the window can reach, runs the
``run_dse`` campaign loop for ``--seconds`` (to the next whole iteration),
checks a seeded sample of the window's evaluations against the plain
reference in ``bench/reference``, and prints one JSON line last on stdout.
``--trace 1`` also profiles the window and reports the per-layer metrics.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from contextlib import contextmanager  # noqa: E402
from pathlib import Path  # noqa: E402

# the TPU runtime logs under /tmp unless told otherwise; a run writes only
# inside its checkout and the directories it is given
os.environ.setdefault("TPU_LOG_DIR", "disabled")

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import spec  # noqa: E402
import tracing  # noqa: E402
import verify  # noqa: E402
import warmup  # noqa: E402
from capture import Capture, neutral_mapping  # noqa: E402

OUT_DIR = ROOT / ".bench_out"
# ``--trace 1`` profiles the window's first whole iterations past this many
# seconds: a trace costs a minute or more to write and read, and a run,
# trace included, has to end within six minutes
TRACE_SECONDS = 15.0


class CompileCounter:
    """XLA compiles and persistent-cache loads, through ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def total(self) -> int:
        return self.compiles + self.cache_hits


class _WindowClosed(Exception):
    pass


class CompileLog(logging.Handler):
    """Names and shapes of the programs JAX compiles while attached."""

    def __init__(self):
        super().__init__()
        self.names: list[str] = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.names.append(msg[len("Compiling "):][:300])

    @contextmanager
    def attached(self):
        import jax
        log = logging.getLogger("jax")
        prev = (jax.config.jax_log_compiles, log.propagate)
        jax.config.update("jax_log_compiles", True)
        log.addHandler(self)
        log.propagate = False
        try:
            yield self
        finally:
            log.removeHandler(self)
            log.propagate = prev[1]
            jax.config.update("jax_log_compiles", prev[0])


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise SystemExit(f"bench: no TPU found (platform "
                         f"{info['platform']!r}); nothing measured")
    if info["count"] < chips:
        raise SystemExit(f"bench: {chips} chips asked for, "
                         f"{info['count']} found; nothing measured")
    return info


def _peak_bytes(chips: int) -> int:
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def warm_all(config: dict, traffic: dict, cons, timed) -> None:
    """Warm every program the cell's traffic can reach (``bench/warmup``)."""
    from repro.core.hardware import HwConfig
    hw = HwConfig.from_tuple(traffic["warm_point"], cons=cons)
    timed("costing", warmup.warm_costing, config, hw)
    timed("scheduler", warmup.warm_scheduler, config, hw)
    if traffic["strategy"] == "nicepim":
        timed("tuner", warmup.warm_tuner, traffic["n_sample"],
              traffic["propose_k"], traffic["max_observations"], cons)


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             device: dict, t_start: float = T_START,
             out_dir: Path = OUT_DIR, warm_up=warm_all) -> dict:
    """Set up, measure and check one run; returns the result dict.

    The traffic's ``pool_seed``, where it has one, seeds the strategy, so
    every run proposes the same design points; ``--seed`` then draws the
    evaluations the check compares.
    """
    import jax
    from repro.core.dse import WorkloadEvaluator, run_dse
    from repro.core.surrogates import make_strategy
    from repro.obs import trace as obs_trace

    config, traffic = cell.config, cell.traffic
    # f32 products in f32, as the configuration states: the TPU's default
    # passes are bf16, under which the tuner's fit diverges (PERF.md)
    jax.config.update("jax_default_matmul_precision",
                      config["matmul_precision"])
    counter = CompileCounter()
    cons = spec.program_constraints(config)
    graph = spec.program_graph(config)
    warm = {}

    def timed(name, fn, *a):
        c0, t0 = counter.total(), time.perf_counter()
        fn(*a)
        warm[name] = {"s": time.perf_counter() - t0,
                      "programs": counter.total() - c0}

    warm_up(config, traffic, cons, timed)
    ce = config["cost_exponents"]
    evaluator = WorkloadEvaluator(
        [graph], alpha=ce["alpha"], beta=ce["beta"], gamma=ce["gamma"],
        mapper_kwargs=spec.mapper_kwargs(config),
        batch_prefill=traffic["batch_prefill"])
    strategy = make_strategy(traffic["strategy"], cons=cons,
                             seed=traffic.get("pool_seed", seed),
                             n_sample=traffic["n_sample"])
    until = traffic["warm_until"]
    st = {"phase": "warm", "filter": 0, "cost": 0, "iters": 0, "obs": [],
          "warm_iters": 0}
    tracer = obs_trace.Tracer() if trace else None
    trace_dir = out_dir / f"trace_{cell.name}_{seed}"
    ann = None

    def stop_trace(now):
        ann.__exit__(None, None, None)
        obs_trace.install(None)
        st["traced"] = (now, st["iters"], evaluator.evaluations - st["evals0"])
        jax.profiler.stop_trace()
        st["t_stopped"] = time.perf_counter()

    def on_iteration(it, obs):
        nonlocal ann
        now = time.perf_counter()
        if st["phase"] == "warm":
            st["warm_iters"] += 1
            if st["warm_iters"] > traffic["max_warm_iterations"]:
                raise SystemExit(
                    f"bench: the tuner had {st['filter']} area and "
                    f"{st['cost']} cost observations after "
                    f"{traffic['max_warm_iterations']} iterations; its "
                    f"models never ranked candidates")
            st["filter"] += len(obs)
            st["cost"] += sum(o.cost is not None and math.isfinite(o.cost)
                              for o in obs)
            if (st["filter"] >= until["filter_observations"]
                    and st["cost"] >= until["cost_observations"]):
                warm["iterations"] = {
                    "s": now - st["t_warm"],
                    "programs": counter.total() - st["c_warm"]}
                st["phase"] = "window"
                st["log"] = compile_log.attached()
                st["log"].__enter__()
                st["compiles0"] = counter.total()
                st["evals0"] = evaluator.evaluations
                if trace:
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    opts.host_tracer_level = 1   # annotations, not runtime
                    jax.profiler.start_trace(str(trace_dir),
                                             profiler_options=opts)
                    obs_trace.install(tracer)
                    ann = jax.profiler.TraceAnnotation(tracing.WINDOW)
                    ann.__enter__()
                st["t0"] = time.perf_counter()
            return
        st["iters"] += 1
        st["obs"].extend(obs)
        if (trace and "traced" not in st
                and now - st["t0"] >= min(seconds, TRACE_SECONDS)):
            stop_trace(now)
        if now - st["t0"] >= seconds:
            st["t1"] = now
            st["evals1"] = evaluator.evaluations
            st["compiles1"] = counter.total()
            st["log"].__exit__(None, None, None)
            raise _WindowClosed

    compile_log = CompileLog()
    capture = Capture()
    st["t_warm"], st["c_warm"] = time.perf_counter(), counter.total()
    with capture.installed():
        try:
            run_dse(strategy, evaluator, iterations=1 << 30,
                    propose_k=traffic["propose_k"], cons=cons,
                    evaluate_all_legal=traffic["evaluate_all_legal"],
                    pipeline=traffic["pipeline"], on_iteration=on_iteration)
        except _WindowClosed:
            pass
    window_s = st["t1"] - st["t0"]
    evals = st["evals1"] - st["evals0"]
    device = dict(device, memory_peak_bytes=_peak_bytes(cell.chips))
    result = {"attempted": evals, "failed": 0, "device": device}
    info = {"setup_s": st["t0"] - t_start, "window_s": window_s,
            "iterations": st["iters"], "warm_iterations": st["warm_iters"],
            "evaluations": evals, "observations": len(st["obs"]),
            "compile_in_window": st["compiles1"] - st["compiles0"],
            "compiled_in_window": compile_log.names[:20],
            "warm_programs": warm}
    if trace:
        t_read = time.perf_counter()
        rows = tracing.load_events(trace_dir)
        t_end, iters, traced_evals = st["traced"]
        info["traced"] = {"s": t_end - st["t0"], "iterations": iters,
                          "evaluations": traced_evals,
                          "stop_s": st["t_stopped"] - t_end,
                          "read_s": time.perf_counter() - t_read}
        bounds = tracing.window_bounds(rows) or (0, 1 << 62)
        dev = tracing.device_rows(rows, *bounds)
        spans = [e for e in tracer.events() if e.get("ph") == "X"]
        names = {e["name"] for e in spans}
        host = [r for r in rows if r["name"] in names
                and not r["plane"].startswith(tracing.DEVICE_PREFIX)]
        ctx = {"spans": spans, "device": dev, "window_s": window_s,
               "trace_window_s": (bounds[1] - bounds[0]) / 1e9,
               "iterations": iters, "evaluations": traced_evals,
               "compiles_in_window": info["compile_in_window"]}
        metrics = {}
        for m in cell.per_layer:
            v = spec.load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        events = [e for evs in dev.values() for e in evs]
        busy = [tracing.busy_ns(evs) / 1e9 for evs in dev.values()]
        result["device"]["busy_s"] = sum(busy) / max(1, len(busy))
        result["device"]["window_s"] = ctx["trace_window_s"]
        result["breakdown"] = {
            "device_ops": tracing.top_programs(events),
            "idle_gaps": tracing.idle_gaps(
                next(iter(dev.values()), []), host, *bounds)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        metrics = {"evals_per_s": {"value": evals / window_s,
                                   "unit": "evals/s"},
                   "setup_s": {"value": info["setup_s"], "unit": "s"}}
        metrics = {m["name"]: metrics[m["name"]] for m in cell.end_to_end}
    result["metrics"] = metrics

    # -- the comparison, once the window has closed --------------------------
    t_check = time.perf_counter()
    gname = graph.name
    areas = [(o.cfg.as_tuple(), o.area_mm2) for o in st["obs"]]
    done = [o for o in st["obs"] if o.cost is not None
            and math.isfinite(o.cost)]
    rng = random.Random(seed)
    sample = []
    if done:
        big = max(done, key=lambda o: o.cfg.na_row * o.cfg.na_col)
        rest = [o for o in done if o is not big]
        sample = [big] + rng.sample(rest, min(len(rest),
                                              traffic["check_sample"] - 1))
    observed = []
    for o in sample:
        got = capture.mappings.get((o.cfg.as_tuple(), gname))
        observed.append({"values": o.cfg.as_tuple(), "cost": o.cost,
                         "mapping": got and neutral_mapping(*got)})
    ref = verify.Reference(config)
    numbers = verify.compare(ref, observed, areas, capture.schedules)
    ok, lines = verify.judge(numbers, cell.limits)
    ok = ok and bool(sample)
    info["checked"] = len(sample)
    info["sched_search"] = verify.search_quality(ref, observed,
                                                 capture.schedules)
    info["choices_differing"] = numbers["choices_differing"]
    info["check_s"] = time.perf_counter() - t_check
    result["correct"] = ok
    result["info"] = info
    result["check"] = {k: {"value": _num(numbers[k]),
                           "limit": cell.limits[k]["limit"]}
                       for k in verify.NUMBERS}
    result["_lines"] = lines + [f"checked {len(sample)} evaluations"]
    result["_capture"] = capture
    result["_sample"] = observed
    result["_areas"] = areas
    return result


def _num(v: float):
    return v if math.isfinite(v) else str(v)


def emit(result: dict) -> None:
    keys = ("correct", "attempted", "failed", "metrics", "device",
            "breakdown", "info", "check")
    line = {k: result[k] for k in keys if k in result}
    for ln in result["_lines"]:
        print(ln, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    from repro import runtime
    runtime.configure_compile_cache(ROOT)
    device = device_info(cell.chips)
    print(f"bench: {args.workload} seed={args.seed} device={device}",
          file=sys.stderr, flush=True)
    emit(run_cell(cell, args.seed, args.seconds, bool(args.trace), device))
    return 0


if __name__ == "__main__":
    sys.exit(main())
