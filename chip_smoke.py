#!/usr/bin/env python3
"""Chip smoke test: the NicePIM DSE main path on a TPU, checked for parity.

    python chip_smoke.py            # one chip: device, costing parity,
                                    # mapper parity, 3-iteration DSE campaign
    python chip_smoke.py --chips 4  # four chips: sharded campaign on a
                                    # 4-device mesh vs the single-device
                                    # pipeline, and nothing else
                                    # (googlenet at 56x56, the Fig. 9 size)

Everything runs in this one process (a second process could not reach the
chip).  Each phase prints one line when it finishes; wall times are host
seconds.  The compile cache goes to ``$JAX_COMPILATION_CACHE_DIR`` or, when
that is unset, to ``.jax_cache`` next to this file.  Any failure exits
non-zero, and so does a run that finds no TPU.  The last line of a passing
run is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

SEED = 0
N_CONFIGS = 24           # Table-II configs in the costing parity phase
DSE_ITERATIONS = 3
DSE_N_SAMPLE = 512       # candidates per proposal, as benchmarks/fig9_dse.py
# sharded phase: each wave evaluates every legal proposal, so the second
# wave already scores candidates with the model (it needs 3 observations).
# What it checks, the propose chain on the mesh, does not depend on the
# graph's size; the graph only feeds observations, and every new mapping
# shape costs a compile at four chips' price, so it maps googlenet at the
# reduced size benchmarks/fig9_dse.py campaigns on (scale 4, 56x56)
SHARDED_ITERATIONS = 2
SHARDED_PROPOSE_K = 5
SHARDED_SCALE = 4
RTOL = 1e-6


class CompileCounter:
    """Counts XLA compiles and persistent-cache hits through jax.monitoring."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def snapshot(self):
        return (self.compiles, self.compile_s, self.cache_hits)

    def since(self, snap) -> str:
        c, s, h = snap
        return (f"xla_programs={self.compiles - c} "
                f"compile_s={self.compile_s - s:.1f} "
                f"cache_hits={self.cache_hits - h}")


def _rel(a: float, b: float) -> float:
    if a == b:
        return 0.0
    return abs(a - b) / max(abs(a), abs(b))


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------


def phase_device(n_chips: int) -> dict:
    import jax
    from repro import runtime
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (platform "
                         f"{dev['platform']!r})")
    if dev["count"] < n_chips:
        raise SystemExit(f"chip_smoke: {n_chips} chips asked for, "
                         f"{dev['count']} found")
    mode = runtime.kernel_mode()
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']} kernels={mode}", flush=True)
    if mode != "native":
        raise SystemExit(f"chip_smoke: kernel mode {mode!r} on a TPU")
    return dev


def _costing_inputs(graph, n_configs: int, seed: int):
    import numpy as np
    from repro.core.hardware import (DEFAULT_CONSTRAINTS, HwConfig,
                                     sample_config_values)
    from repro.core.layout import DataLayout
    from repro.engine import PartSpec
    rng = np.random.default_rng(seed)
    vals = sample_config_values(n_configs, rng, DEFAULT_CONSTRAINTS)
    configs = [HwConfig.from_tuple(tuple(int(v) for v in row),
                                   cons=DEFAULT_CONSTRAINTS) for row in vals]
    dls = [DataLayout("BCHW", 1), DataLayout("BCHW", 8), DataLayout("BHWC"),
           DataLayout("BCHW", 16)]
    specs = [PartSpec(l, dls[i % 4], dls[(i + 1) % 4])
             for i, l in enumerate(graph.layers)]
    return configs, specs


def phase_costing(graph, counter, n_configs: int = N_CONFIGS,
                  seed: int = SEED) -> None:
    """Grid and paired device costing against the host scalar cost model."""
    from repro.core.costmodel import part_layer_cost
    from repro.engine.batch_cost import (batch_part_cost,
                                         batch_part_cost_paired)
    t0 = time.perf_counter()
    snap = counter.snapshot()
    configs, specs = _costing_inputs(graph, n_configs, seed)
    grid = batch_part_cost(configs, specs)
    pairs = [(i, j) for i in range(len(configs)) for j in range(len(specs))]
    paired = batch_part_cost_paired([configs[i] for i, _ in pairs],
                                    [specs[j] for _, j in pairs])
    t_dev = time.perf_counter() - t0
    fields = ("latency_s", "energy_pj", "compute_s", "dram_s", "dram_bytes",
              "e_mac_pj", "e_sram_pj", "e_dram_pj")
    worst = {f: 0.0 for f in fields}
    bad = []
    for k, (i, j) in enumerate(pairs):
        s = specs[j]
        ref = part_layer_cost(configs[i], s.layer, s.dl_in, s.dl_out)
        for got in (grid.part_cost(i, j), paired.part_cost(0, k)):
            errs = {f: _rel(getattr(ref, f), getattr(got, f)) for f in fields}
            worst = {f: max(worst[f], errs[f]) for f in fields}
            err = max(errs.values())
            if (err > RTOL or ref.tiling != got.tiling
                    or ref.loop_order != got.loop_order):
                bad.append((configs[i].as_tuple(), s.layer.name))
    print(f"costing: graph={graph.name} configs={len(configs)} "
          f"specs={len(specs)} pairs={len(pairs)} modes=grid+paired "
          f"max_rel_err={max(worst.values()):.3e} "
          f"latency_rel_err={worst['latency_s']:.3e} mismatches={len(bad)} "
          f"device_s={t_dev:.1f} wall_s={time.perf_counter() - t0:.1f} "
          f"{counter.since(snap)}", flush=True)
    if bad:
        raise SystemExit(f"chip_smoke: costing parity failed on "
                         f"{len(bad)} (config, layer) pairs, e.g. {bad[:3]}")


def phase_mapper(graph, counter, mapper_kwargs: dict) -> None:
    """Batched mapper (device costing) against the scalar mapper (host)."""
    from repro.core.hardware import PAPER_BEST
    from repro.core.mapper import PimMapper, clear_mapper_caches
    t0 = time.perf_counter()
    snap = counter.snapshot()
    clear_mapper_caches()
    mb = PimMapper(PAPER_BEST, backend="batched", **mapper_kwargs).map(graph)
    t_batched = time.perf_counter() - t0
    clear_mapper_caches()
    t1 = time.perf_counter()
    ms = PimMapper(PAPER_BEST, backend="scalar", **mapper_kwargs).map(graph)
    t_scalar = time.perf_counter() - t1
    clear_mapper_caches()
    diffs = []
    if ms.sm != mb.sm:
        diffs.append(("sm", ms.sm, mb.sm))
    if set(ms.choices) != set(mb.choices):
        diffs.append(("choices", sorted(ms.choices), sorted(mb.choices)))
    worst = _rel(ms.est_latency_s, mb.est_latency_s)
    for name, cs in ms.choices.items():
        cb = mb.choices.get(name)
        if cb is None:
            continue
        key_s = (cs.lm, cs.wr, cs.region, cs.dl_in, cs.dl_out)
        key_b = (cb.lm, cb.wr, cb.region, cb.dl_in, cb.dl_out)
        if key_s != key_b:
            diffs.append((name, "scalar", key_s, cs.perf_s,
                          "batched", key_b, cb.perf_s))
        worst = max(worst, _rel(cs.perf_s, cb.perf_s),
                    _rel(cs.size_bytes, cb.size_bytes))
    print(f"mapper: graph={graph.name} hw={PAPER_BEST.as_tuple()} "
          f"layers={len(ms.choices)} est_latency_s={mb.est_latency_s!r} "
          f"max_rel_err={worst:.3e} choice_diffs={len(diffs)} "
          f"batched_s={t_batched:.1f} scalar_host_s={t_scalar:.1f} "
          f"{counter.since(snap)}", flush=True)
    if diffs or worst > RTOL:
        raise SystemExit(f"chip_smoke: mapper parity failed: {diffs[:3]} "
                         f"max_rel_err={worst:.3e}")


def phase_dse(graph, counter, mapper_kwargs: dict,
              iterations: int = DSE_ITERATIONS,
              n_sample: int = DSE_N_SAMPLE) -> None:
    """Three NicePIM iterations through the device-resident pipeline."""
    from repro.core.dse import WorkloadEvaluator, run_dse
    from repro.core.tuner import PimTuner
    from repro.engine import engine_program_counts
    t0 = time.perf_counter()
    snap = counter.snapshot()
    ev = WorkloadEvaluator([graph], mapper_kwargs=dict(mapper_kwargs))
    res = run_dse(PimTuner(seed=SEED, n_sample=n_sample, backend="scan"), ev,
                  iterations=iterations, pipeline=True)
    wall = time.perf_counter() - t0
    iters = sorted({o.iteration for o in res.observations})
    costs = [o.cost for o in res.observations if o.cost is not None]
    best = res.best().cost if costs else math.inf
    print(f"dse: graph={graph.name} strategy=nicepim pipeline=True "
          f"iterations={len(iters)} observations={len(res.observations)} "
          f"evaluated={len(costs)} best_edp={best!r} "
          f"engine_programs={sum(engine_program_counts().values())} "
          f"wall_s={wall:.1f} {counter.since(snap)}", flush=True)
    if iters != list(range(iterations)):
        raise SystemExit(f"chip_smoke: DSE ran iterations {iters}")
    if not costs or not all(c > 0 and math.isfinite(c) for c in costs):
        raise SystemExit(f"chip_smoke: DSE costs not finite: {costs}")


def phase_sharded(graph, counter, mapper_kwargs: dict, n_chips: int,
                  iterations: int = SHARDED_ITERATIONS,
                  propose_k: int = SHARDED_PROPOSE_K,
                  n_sample: int = DSE_N_SAMPLE) -> None:
    """Two tenants on a ``config`` mesh vs the same streams on one device."""
    import numpy as np
    from repro.core.dse import WorkloadEvaluator, run_dse
    from repro.core.surrogates import make_strategy
    from repro.engine import (ParetoFront, ShardedCampaign, TenantSpec,
                              campaign_mesh, engine_program_counts,
                              shard_config_rows)
    t0 = time.perf_counter()
    snap = counter.snapshot()
    mesh = campaign_mesh(n_chips)
    tenants = [TenantSpec(name=f"t{seed}", workloads=[graph], seed=seed,
                          iterations=iterations, propose_k=propose_k,
                          n_sample=n_sample, evaluate_all_legal=True,
                          evaluator_kwargs=dict(
                              mapper_kwargs=dict(mapper_kwargs)))
               for seed in (11, 12)]
    # candidate rows must really split over the mesh: a row count the
    # device count does not divide would be replicated instead
    rows = shard_config_rows(mesh, np.zeros((n_sample, 7), np.float32))
    placed = len(rows.sharding.device_set)
    if rows.sharding.spec != ("config",) or placed != n_chips:
        raise SystemExit(f"chip_smoke: candidate rows sit on {placed} of "
                         f"{n_chips} devices")
    out = ShardedCampaign(tenants, mesh=mesh).run()
    t_sharded = time.perf_counter() - t0
    # the model must have scored candidates on the mesh, not only sampled
    scored = sum(v for k, v in engine_program_counts().items()
                 if k.startswith("sharded.scores"))

    def stream(obs):
        return [(o.iteration, o.cfg.as_tuple(), o.area_mm2, o.legal, o.cost)
                for o in obs]

    def front(f):
        return sorted((p.latency_s, p.energy_pj, p.area_mm2)
                      for p in f.points)

    t1 = time.perf_counter()
    single_front = ParetoFront()
    diverged = []
    for spec in tenants:
        ev = WorkloadEvaluator(list(spec.workloads),
                               clear_caches_between_configs=True,
                               **spec.evaluator_kwargs)
        res = run_dse(make_strategy("nicepim", cons=spec.cons, seed=spec.seed,
                                    n_sample=spec.n_sample),
                      ev, iterations=spec.iterations,
                      propose_k=spec.propose_k, pareto=single_front,
                      evaluate_all_legal=spec.evaluate_all_legal,
                      pipeline=True)
        if stream(res.observations) != stream(
                out.results[spec.name].observations):
            diverged.append(spec.name)
    t_single = time.perf_counter() - t1
    same_front = front(out.pareto) == front(single_front)
    n_obs = sum(len(r.observations) for r in out.results.values())
    print(f"sharded: graph={graph.name} mesh=config:{mesh.devices.size} "
          f"rows_on_devices={placed} tenants={len(tenants)} "
          f"iterations={iterations} observations={n_obs} "
          f"streams_identical={not diverged} "
          f"pareto_identical={same_front} "
          f"pareto_points={len(out.pareto.points)} "
          f"mesh_scoring_programs={scored} "
          f"sharded_s={t_sharded:.1f} single_device_s={t_single:.1f} "
          f"{counter.since(snap)}", flush=True)
    if not scored:
        raise SystemExit("chip_smoke: no wave scored candidates on the mesh")
    if diverged or not same_front:
        raise SystemExit(f"chip_smoke: sharded campaign diverged from the "
                         f"single-device pipeline ({diverged}, "
                         f"pareto_identical={same_front})")


# --------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-campaign phase on a "
                         "four-chip mesh")
    args = ap.parse_args(argv)

    import jax
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from repro import runtime
    cache_dir = runtime.configure_compile_cache(ROOT)
    counter = CompileCounter()
    t_start = time.perf_counter()
    dev = phase_device(args.chips)
    print(f"compile cache: {cache_dir}", flush=True)

    from benchmarks.fig9_dse import MAPPER_KWARGS
    from repro.core.workloads import googlenet
    if args.chips == 4:
        phase_sharded(googlenet(1, scale=SHARDED_SCALE), counter,
                      MAPPER_KWARGS, args.chips)
    else:
        graph = googlenet(1)
        phase_costing(graph, counter)
        phase_mapper(graph, counter, MAPPER_KWARGS)
        phase_dse(graph, counter, MAPPER_KWARGS)
    print(f"total: wall_s={time.perf_counter() - t_start:.1f} "
          f"xla_programs={counter.compiles} "
          f"compile_s={counter.compile_s:.1f} "
          f"cache_hits={counter.cache_hits}", flush=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
