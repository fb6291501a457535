"""The full NicePIM DSE loop (paper Fig. 7) on reduced workloads.

Iterates: PIM-Tuner samples + filters + ranks hardware configs -> the
area "simulator" validates -> PIM-Mapper + Data-Scheduler produce mapping
schemes and EDP costs -> the tuner's DKL/filter models are refit.

    PYTHONPATH=src python examples/dse_nicepim.py [--iters 8] [--all-legal]
                                                  [--tuner-backend loop]
                                                  [--scheduler-backend loop]
                                                  [--trace out.json]

``--all-legal`` maps EVERY legal proposal per iteration in one multi-config
batch (``WorkloadEvaluator.evaluate_batch`` / ``PimMapper.map_many``) instead
of the paper's first-legal-only walk — more observations per DKL refit.
``--tuner-backend loop`` swaps the jitted scan tuner engine for the scalar
per-step reference path (same-seed results match within float drift).
``--scheduler-backend loop`` swaps the jitted engine Data-Scheduler for the
host-Python 2-opt reference (different RNG streams: close, not identical).
``--trace out.json`` records propose/map/schedule/evaluate spans to a
Chrome-trace file — open it in Perfetto (https://ui.perfetto.dev) or
chrome://tracing to see where the loop spends its time.
"""

import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

from repro.core.dse import WorkloadEvaluator, run_dse
from repro.core.mapper import mapper_cache_stats
from repro.core.tuner import PimTuner
from repro.core.workloads import bert_base, googlenet
from repro.engine.cache import EvalCache
from repro.engine.tuner_train import compiled_program_count
from repro.obs.trace import Tracer
from repro.runtime import configure_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--all-legal", action="store_true",
                    help="map every legal proposal per iteration "
                         "(multi-config batched mapping)")
    ap.add_argument("--tuner-backend", default="scan",
                    choices=("scan", "loop"),
                    help="jitted scan tuner engine (default) or the scalar "
                         "per-step reference loop")
    ap.add_argument("--scheduler-backend", default="scan",
                    choices=("scan", "loop"),
                    help="jitted engine Data-Scheduler (default) or the "
                         "host-Python 2-opt reference")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="write a Chrome-trace of the run here "
                         "(Perfetto / chrome://tracing)")
    args = ap.parse_args()
    configure_compile_cache(ROOT)

    workloads = [googlenet(1, scale=4),
                 bert_base(1, seq=64, n_layers=2, n_heads=4)]
    cache = EvalCache()
    evaluator = WorkloadEvaluator(
        workloads, mapper_kwargs=dict(max_optim_iter=1, lm_cap=60, n_wr=3),
        scheduler_backend=args.scheduler_backend, cache=cache)
    tuner = PimTuner(n_sample=512, backend=args.tuner_backend)
    tracer = Tracer() if args.trace else None
    res = run_dse(tuner, evaluator, iterations=args.iters, verbose=True,
                  evaluate_all_legal=args.all_legal, tracer=tracer)
    if tracer is not None:
        tracer.save(args.trace)
    best = res.best()
    print("\nbest architecture found:")
    print(f"  node array : {best.cfg.na_row}x{best.cfg.na_col} "
          f"({best.cfg.banks_per_node} banks/node)")
    print(f"  PE array   : {best.cfg.pea_row}x{best.cfg.pea_col}")
    print(f"  buffers    : i={best.cfg.ibuf_kib} w={best.cfg.wbuf_kib} "
          f"o={best.cfg.obuf_kib} KiB")
    print(f"  area       : {best.area_mm2:.1f} mm^2 (budget 48)")
    print(f"  EDP cost   : {best.cost:.3e}")
    print(f"  quality curve: "
          f"{['%.2e' % q for q in res.quality_curve()]}")

    stats = cache.stats
    total = stats["hits"] + stats["misses"]
    memo = mapper_cache_stats()
    print("\nrun telemetry:")
    print(f"  eval cache : {stats['hits']}/{total} hits "
          f"({stats['entries']} entries)")
    print(f"  xla jit    : {sum(compiled_program_count().values())} "
          f"compiled programs {compiled_program_count()}")
    print(f"  mapper memo: {sum(memo.values())} entries {memo}")
    if args.trace:
        print(f"  trace      : {args.trace} "
              "(open in https://ui.perfetto.dev)")


if __name__ == "__main__":
    main()
