"""Assemble EXPERIMENTS.md from dry-run JSONs + benchmark results.

Sections:
  §Dry-run          — compile status, memory per device, collective schedule
  §Roofline         — three terms per (arch x shape x mesh), bottleneck, MFU
  §Paper            — Fig. 9/10/11/12 reproductions vs the paper's claims
  §Sharded-campaign — BENCH_9 mega-campaign speedup + kill/resume contract
  §Overlap          — BENCH_10 overlapped-executor speedup + parity contract
  §Perf-trajectory  — named regression gates per BENCH_*.json artifact
  §Perf             — hillclimb log (benchmarks/perf_log.py entries)
"""

from __future__ import annotations

import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
DRYRUN_DIR = ROOT / "experiments" / "dryrun"
PERF_DIR = ROOT / "experiments" / "perf"
PAPER_JSON = ROOT / "experiments" / "paper_benchmarks.json"
OUT = ROOT / "EXPERIMENTS.md"


def _fmt_bytes(b: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(b) < 1024 or unit == "TiB":
            return f"{b:.2f}{unit}"
        b /= 1024
    return f"{b:.1f}"


def load_dryrun() -> list[dict]:
    if not DRYRUN_DIR.exists():
        return []
    return sorted((json.loads(p.read_text())
                   for p in DRYRUN_DIR.glob("*.json")),
                  key=lambda d: (d["arch"], d["shape"], d["mesh"]))


def dryrun_section(cells: list[dict]) -> str:
    lines = [
        "## §Dry-run",
        "",
        "`.lower().compile()` on the production meshes (single-pod 16x16 = "
        "256 chips; multi-pod 2x16x16 = 512 chips) with 512 host placeholder "
        "devices. `mem/dev` = args + temps + outputs - aliased from "
        "`compiled.memory_analysis()` of the SPMD-partitioned (per-device) "
        "program.",
        "",
        "| arch | shape | mesh | status | compile_s | mem/dev | collectives (per-chip link bytes) |",
        "|---|---|---|---|---|---|---|",
    ]
    for c in cells:
        if c.get("status") != "ok":
            lines.append(f"| {c['arch']} | {c['shape']} | {c['mesh']} | "
                         f"FAIL: {c.get('error', '?')[:60]} | | | |")
            continue
        mem = c["memory"]
        per_dev = (mem.get("argument_size_in_bytes", 0)
                   + mem.get("temp_size_in_bytes", 0)
                   + mem.get("output_size_in_bytes", 0)
                   - mem.get("alias_size_in_bytes", 0))
        colls = ", ".join(f"{k.split('-')[-1]}={_fmt_bytes(v)}"
                          for k, v in c["collectives"].items() if v)
        lines.append(
            f"| {c['arch']} | {c['shape']} | {c['mesh']} | ok | "
            f"{c['compile_s']:.1f} | {_fmt_bytes(per_dev)} | {colls or '-'} |")
    skips = _skips()
    if skips:
        lines += ["", "Skipped cells (documented in DESIGN.md "
                      "§Arch-applicability):", ""]
        for a, s, why in skips:
            lines.append(f"- `{a}` x `{s}`: {why}")
    return "\n".join(lines)


def _skips():
    try:
        import sys
        sys.path.insert(0, str(ROOT / "src"))
        from repro.configs.base import skipped_cells
        return skipped_cells()
    except Exception:
        return []


def roofline_section(cells: list[dict]) -> str:
    lines = [
        "## §Roofline",
        "",
        "Terms per the spec: compute = HLO_FLOPs/(chips*197 TF/s), memory = "
        "HLO_bytes/(chips*819 GB/s), collective = per-chip link bytes / "
        "50 GB/s. FLOPs/bytes come from the unrolled cost-fidelity pass "
        "(XLA cost_analysis counts while bodies once); `useful` = "
        "MODEL_FLOPS/HLO_FLOPs; `frac` = ideal-compute-time / max(term).",
        "",
        "| arch | shape | mesh | compute_s | memory_s | collective_s | "
        "bottleneck | useful | frac | note |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for c in cells:
        if c.get("status") != "ok":
            continue
        r = c["roofline"]
        lines.append(
            f"| {c['arch']} | {c['shape']} | {c['mesh']} | "
            f"{r['compute_s']:.4f} | {r['memory_s']:.4f} | "
            f"{r['collective_s']:.4f} | **{r['bottleneck']}** | "
            f"{r['useful_ratio']:.2f} | {r['roofline_fraction']:.4f} | "
            f"{r.get('note', '')[:60]} |")
    return "\n".join(lines)


def paper_section() -> str:
    if not PAPER_JSON.exists():
        return "## §Paper-experiments\n\n(run `python -m benchmarks.run`)"
    rows = json.loads(PAPER_JSON.read_text())
    lines = ["## §Paper-experiments", ""]
    fig10 = [r for r in rows if r.get("table") == "fig10"]
    if fig10:
        avg = [r for r in fig10 if r.get("net") == "all"]
        lines += ["### Fig. 10 — PIM-Mapper vs sequential baseline "
                  "(paper: −37 % latency / −28 % energy avg)", "",
                  "| system | net | mapper lat (ms) | base lat (ms) | ΔLat | "
                  "mapper E (uJ) | base E (uJ) | ΔE |",
                  "|---|---|---|---|---|---|---|---|"]
        for r in fig10:
            if r.get("net") == "all":
                continue
            lines.append(
                f"| {r['system']} | {r['net']} | "
                f"{r['mapper_latency_ms']:.2f} | "
                f"{r['baseline_latency_ms']:.2f} | "
                f"{-r['latency_reduction']:.0%} | "
                f"{r['mapper_energy_uj']:.0f} | "
                f"{r['baseline_energy_uj']:.0f} | "
                f"{-r['energy_reduction']:.0%} |")
        if avg:
            lines.append(f"| **avg** | | | | "
                         f"**{-avg[0]['latency_reduction']:.0%}** | | | "
                         f"**{-avg[0]['energy_reduction']:.0%}** |")
        lines.append("")
    fig9 = [r for r in rows
            if r.get("table") == "fig9" and "quality_final" in r]
    if fig9:
        lines += ["### Fig. 9 — DSE quality (mean 1/cost of best-3; "
                  "higher is better)", "",
                  "| strategy | final quality | vs random |", "|---|---|---|"]
        base = next((r["quality_final"] for r in fig9
                     if r["strategy"] == "random"), 1e-30)
        for r in fig9:
            lines.append(f"| {r['strategy']} | {r['quality_final']:.3e} | "
                         f"{r['quality_final'] / max(base, 1e-30):.2f}x |")
        lines.append("")
    par = next((r for r in rows if r.get("table") == "fig9"
                and r.get("strategy") == "pareto"), None)
    if par:
        lines += [f"Campaign Pareto front: {par['pareto_size']} points; "
                  f"eval cache: {par['cache']['hits']} hits / "
                  f"{par['cache']['misses']} misses.", ""]
        lines += _campaign_metrics(par)
    eng = [r for r in rows if r.get("table") == "engine"]
    if eng:
        r = eng[-1]
        lines += ["### Engine — batched vs scalar cost-model throughput", "",
                  "| path | configs/sec | speedup |", "|---|---|---|",
                  f"| scalar per-candidate loop | "
                  f"{r['scalar_configs_per_s']:.1f} | 1.0x |",
                  f"| batched engine ({r['n_configs']} cfgs x "
                  f"{r['n_layers']} part-layers) | "
                  f"{r['batched_configs_per_s']:.1f} | "
                  f"{r['speedup']:.1f}x |", ""]
    mapper = [r for r in rows if r.get("table") == "mapper"]
    if mapper:
        r = mapper[-1]
        lines += ["### Mapper — batched vs scalar candidate costing", "",
                  f"(LM x WR) candidate points per second over "
                  f"{r['n_sweeps']} DL-alternation sweeps of "
                  f"{r['n_layers']} layers on a "
                  f"{r['region'][0]}x{r['region'][1]} region "
                  f"(contract: >=10x).", "",
                  "| path | candidates/sec | speedup |", "|---|---|---|",
                  f"| scalar per-candidate loop | "
                  f"{r['scalar_cands_per_s']:.0f} | 1.0x |",
                  f"| batched backend | {r['batched_cands_per_s']:.0f} | "
                  f"{r['speedup']:.1f}x |", "",
                  f"End-to-end `PimMapper.map` (googlenet): "
                  f"{r['map_speedup']:.2f}x faster batched.", ""]
    multi = [r for r in rows if r.get("table") == "mapper_multi"]
    if multi:
        r = multi[-1]
        lines += ["### Mapper — multi-config batched mapping "
                  "(`PimMapper.map_many`)", "",
                  f"End-to-end maps/sec over a batch of {r['batch']} "
                  f"proposal configs (googlenet, one optimization pass); "
                  f"contract: >=3x vs the scalar sequential reference at "
                  f"batch >= 8.", "",
                  "| path | maps/sec | speedup |", "|---|---|---|",
                  f"| scalar sequential per-config `map()` | "
                  f"{r['batch'] / r['scalar_seq_s']:.2f} | 1.0x |",
                  f"| batched sequential per-config `map()` | "
                  f"{r['maps_per_s_seq']:.2f} | "
                  f"{r['scalar_seq_s'] / r['seq_s']:.2f}x |",
                  f"| `map_many` (one multi-config batch) | "
                  f"{r['maps_per_s_batched']:.2f} | "
                  f"{r['speedup']:.2f}x |", ""]
    tuner = [r for r in rows if r.get("table") == "tuner"]
    if tuner:
        r = tuner[-1]
        progs = ", ".join(f"{k}={v}" for k, v in r["programs"].items() if v)
        lines += ["### Tuner — jitted scan engine vs scalar loop "
                  "(propose + fit per DSE iteration)", "",
                  f"Growing-dataset schedule to {r['n_obs_final']} "
                  f"observations, {r['n_sample']} candidates/propose; "
                  f"throughput measured at >={r['min_obs']} observations "
                  f"(contract: >=5x; pow2-bucket program bound "
                  f"{r['program_bound']} per entry point).", "",
                  "| path | iterations/sec | speedup |", "|---|---|---|",
                  f"| scalar loop (per-step dispatch, retrace per size) | "
                  f"{r['loop_iters_per_s']:.2f} | 1.0x |",
                  f"| scan engine (pow2-bucketed, fused propose) | "
                  f"{r['engine_iters_per_s']:.2f} | "
                  f"{r['speedup']:.1f}x |", "",
                  f"XLA programs compiled by the engine across the run: "
                  f"{progs or 'none (warm cache)'}.", ""]
    sched = [r for r in rows if r.get("table") == "scheduler"]
    if sched:
        tot = next((r for r in sched if r["case"] == "batched_total"), None)
        lines += ["### Scheduler — jitted scan engine vs host-Python loop "
                  "(joint 2-opt solves)", "",
                  "Fig. 12 singles at the paper budget; batched = one "
                  "pow2-bucketed `schedule_many` call over chunk-scaled "
                  "problem variants (contract: >=5x batched solve "
                  "throughput; scan objective <= loop on every array). "
                  "The 16x16 array's 960 dense link loads made the scan "
                  "memory-bound on CPU before PR 7 (~0.9x vs loop, 239 ms "
                  "per solve); the int16 flip-cumsum + streamed delta "
                  "scoring hold it at >=1x (asserted; ~1.7x / ~107 ms "
                  "measured on the `jnp-dense` path — `pallas-stream` is "
                  "the TPU path).", "",
                  "| case | path | scan (ms) | loop (ms) | speedup |",
                  "|---|---|---|---|---|"]
        for r in sched:
            if r["case"] == "batched_total":
                continue
            tag = (f"{r['case']} (batch {r['batch']})"
                   if "batch" in r else r["case"])
            lines.append(f"| {tag} | {r.get('path', '-')} | "
                         f"{r['scan_s'] * 1e3:.0f} | "
                         f"{r['loop_s'] * 1e3:.0f} | "
                         f"{r['speedup']:.1f}x |")
        if tot:
            lines.append(f"| **batched total ({tot['n_solves']} solves)** | "
                         f"- | "
                         f"{tot['scan_s'] * 1e3:.0f} | "
                         f"{tot['loop_s'] * 1e3:.0f} | "
                         f"**{tot['speedup']:.1f}x** |")
        lines.append("")
    fig11 = [r for r in rows if r.get("table") == "fig11"]
    if fig11:
        lines += ["### Fig. 11 — throughput vs DDAM-lite "
                  "(paper: +11 % avg, ~10x latency gap)", "",
                  "| net | thr gain | DDAM/mapper latency |", "|---|---|---|"]
        for r in fig11:
            lines.append(f"| {r['net']} | {r['throughput_gain']:+.0%} | "
                         f"{r['latency_ratio']:.1f}x |")
        lines.append("")
    fig12 = [r for r in rows if r.get("table") == "fig12"]
    if fig12:
        lines += ["### Fig. 12 — data-sharing schedulers "
                  "(latency normalized to ILP)", "",
                  "Ordering (ILP <= TSP <= SHP) reproduces; magnitudes are "
                  "muted vs the paper because our NoC model charges "
                  "aggregate link load (the paper's Eq. 4 objective) while "
                  "BookSim's flit-level simulation adds serialization and "
                  "in-flight contention that penalize SHP/TSP further.", "",
                  "| array | ilp | tsp | shp |", "|---|---|---|---|"]
        arrays = sorted({r["array"] for r in fig12},
                        key=lambda a: int(a.split("x")[0]))
        for a in arrays:
            sub = {r["method"]: r for r in fig12 if r["array"] == a}
            lines.append(
                f"| {a} | 1.00 | {sub['tsp']['norm_latency']:.2f} | "
                f"{sub['shp']['norm_latency']:.2f} |")
    return "\n".join(lines)


def _fmt_metric(v) -> str:
    if isinstance(v, dict):  # histogram summary {count, sum, min, max, mean}
        return (f"n={v.get('count', 0)} mean={v.get('mean', 0):.3g} "
                f"[{v.get('min', 0):.3g}, {v.get('max', 0):.3g}]")
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v)


def _campaign_metrics(par: dict) -> list[str]:
    """Selected registry metrics from the Fig. 9 campaign's pareto row."""
    metrics = par.get("metrics") or {}
    if not metrics:
        return []
    keep = [k for k in sorted(metrics)
            if k.startswith(("eval_cache.", "pareto.", "campaign."))
            or k.endswith((".best_cost", ".legal_fraction"))
            or k.startswith("tuner.bucket_fill")]
    lines = ["Campaign telemetry (metrics registry snapshot):", "",
             "| metric | value |", "|---|---|"]
    for k in keep:
        lines.append(f"| `{k}` | {_fmt_metric(metrics[k])} |")
    progs = par.get("programs") or {}
    if progs:
        lines.append(f"| `xla.programs` (total) | {sum(progs.values())} |")
    lines.append("")
    return lines


def campaign_section() -> str:
    """§Sharded-campaign: the BENCH_9 mega-campaign contract."""
    f = ROOT / "experiments" / "BENCH_9.json"
    lines = ["## §Sharded-campaign", ""]
    if not f.exists():
        return "\n".join(lines + [
            "(run `python -m benchmarks.campaign_throughput`)"])
    try:
        b = json.loads(f.read_text())
    except json.JSONDecodeError:
        return "\n".join(lines + ["(BENCH_9.json unreadable)"])
    by_name = {r["name"]: r for r in b.get("benchmarks", [])}
    gate = b.get("gates", {}).get("campaign_sharded_speedup", {})
    lines += [
        "Multi-tenant DSE service (`repro.engine.sharded.ShardedCampaign`): "
        "repeated tenant submissions on a 4-device `config` mesh with async "
        "wave overlap and one shared `PersistentEvalCache`, vs the same "
        "submissions run sequentially single-stream.  Observation streams "
        "and the Pareto front are asserted identical; a mid-campaign "
        "`os._exit` kill resumes with zero re-evaluated points "
        "(replay-by-re-proposal against the durable sqlite table).", "",
        "| case | result |", "|---|---|",
    ]
    sh = by_name.get("campaign_sharded")
    if sh:
        lines.append(f"| sharded vs single-stream | {sh['derived']} "
                     f"({b.get('mode', '?')} mode, gate floor "
                     f"{gate.get('value', 0):.2f} - "
                     f"{gate.get('tolerance', 0):.0%}) |")
    kr = by_name.get("campaign_kill_resume")
    if kr:
        lines.append(f"| kill-and-resume | {kr['derived']} |")
    return "\n".join(lines + [""])


def overlap_section() -> str:
    """§Overlap: the BENCH_10 overlapped-wave-executor contract."""
    f = ROOT / "experiments" / "BENCH_10.json"
    lines = ["## §Overlap", ""]
    if not f.exists():
        return "\n".join(lines + [
            "(run `python -m benchmarks.overlap_throughput`)"])
    try:
        b = json.loads(f.read_text())
    except json.JSONDecodeError:
        return "\n".join(lines + ["(BENCH_10.json unreadable)"])
    by_name = {r["name"]: r for r in b.get("benchmarks", [])}
    gate = b.get("gates", {}).get("overlap_speedup", {})
    lines += [
        "Overlapped wave executor (`repro.engine.overlap.OverlapExecutor`): "
        "`map_many` paired cost sweeps dispatched async so wave *k*'s "
        "device costing is in flight while the host runs wave *k−1*'s "
        "backtracking / scheduling, with iteration *k+1*'s fused propose "
        "chain double-buffered behind iteration *k*'s ingest.  Observation "
        "streams and Pareto fronts vs the serial executor are asserted "
        "identical bit for bit; the throughput contract is >=1.3x warm "
        "iterations on a multi-core host (break-even on single-core — "
        "there is no second core to hide latency on).", "",
        "| case | result |", "|---|---|",
    ]
    ov = by_name.get("overlap_warm_iter")
    if ov:
        lines.append(f"| overlapped vs serial warm campaign | "
                     f"{ov['derived']} ({b.get('mode', '?')} mode, gate "
                     f"{gate.get('value', 0):.2f} - "
                     f"{gate.get('tolerance', 0):.0%}) |")
    return "\n".join(lines + [""])


def bench_section() -> str:
    """§Perf-trajectory: the named gates in each BENCH_*.json artifact."""
    lines = ["## §Perf-trajectory", ""]
    files = sorted((ROOT / "experiments").glob("BENCH_*.json"))
    if not files:
        return "\n".join(lines + [
            "(no BENCH artifacts yet — run `python -m benchmarks.run`)"])
    lines += [
        "Machine-readable perf artifacts written by `benchmarks.run` and "
        "gated in CI by `benchmarks.bench_gate` (a gate regresses when it "
        "falls below `baseline * (1 - tolerance)`).", "",
        "| artifact | mode | gate | value | tolerance |", "|---|---|---|---|---|"]
    for f in files:
        try:
            b = json.loads(f.read_text())
        except json.JSONDecodeError:
            lines.append(f"| {f.name} | ? | (unreadable) | | |")
            continue
        gates = b.get("gates", {})
        for i, (name, g) in enumerate(sorted(gates.items())):
            tag = f.name if i == 0 else ""
            mode = b.get("mode", "?") if i == 0 else ""
            lines.append(f"| {tag} | {mode} | `{name}` | "
                         f"{g['value']:.2f} | {g.get('tolerance', 0):.0%} |")
        secs = b.get("sections_s", {})
        if secs:
            total = sum(secs.values())
            lines.append(f"| | | _wall_ | {total:.0f}s | |")
    return "\n".join(lines + [""])


def perf_section() -> str:
    lines = ["## §Perf", ""]
    if not PERF_DIR.exists():
        return "\n".join(lines + ["(no hillclimb entries yet)"])
    entries = sorted(PERF_DIR.glob("*.md"))
    for e in entries:
        lines.append(e.read_text().rstrip())
        lines.append("")
    return "\n".join(lines)


def build() -> str:
    cells = load_dryrun()
    parts = [
        "# EXPERIMENTS",
        "",
        "Generated by `benchmarks/report.py` from `experiments/` artifacts. "
        "Hardware constants: TPU v5e-class — 197 TFLOP/s bf16, 819 GB/s HBM, "
        "50 GB/s/link ICI.",
        "",
        dryrun_section(cells),
        "",
        roofline_section(cells),
        "",
        paper_section(),
        "",
        campaign_section(),
        "",
        overlap_section(),
        "",
        bench_section(),
        "",
        perf_section(),
    ]
    return "\n".join(parts) + "\n"


def main() -> None:
    OUT.write_text(build())
    print(f"wrote {OUT}")


if __name__ == "__main__":
    main()
