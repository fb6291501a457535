"""PIM-Tuner propose+fit throughput: jitted scan engine vs scalar loop.

The tuner is the DSE loop's per-iteration fixed cost: refit the filter MLP
(200 Adam steps) and the DKL suggestion model (300 Adam steps), then sample
and score a fresh candidate batch.  The scalar reference path dispatches
every Adam step from the host AND retraces both training steps (plus the GP
predict) on every *growing* dataset shape — one fresh XLA program per DSE
iteration.  The engine path (``backend="scan"``) runs each fit as one jitted
``lax.scan`` over pow2-bucketed masked data and scores candidates in one
fused dispatch, so a whole campaign compiles O(log n) distinct programs.

``run()`` drives both backends through the same growing-dataset DSE schedule
(observations accumulate every iteration, exactly the shape pattern
``run_dse`` produces) and enforces two contracts outside ``--smoke``:

* >=5x propose+fit throughput once >=30 observations have accumulated
  (``assert_5x``), and
* the engine's XLA program count across the whole run stays within the
  pow2-bucket bound ``log2(final bucket) + 2`` per entry point
  (``repro.engine.tuner_train.compiled_program_count``).

Costs are synthetic (a smooth deterministic function of the config tuple) —
this benchmark isolates tuner throughput; mapper throughput has its own
harness.
"""

from __future__ import annotations

import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np

from repro.core.hardware import sample_configs_batch
from repro.core.tuner import PimTuner
from repro.engine.tuner_train import compiled_program_count, pow2_bucket


def _synthetic_cost(cfg) -> float:
    """Smooth, deterministic stand-in for the mapper's Eq. 1 cost."""
    t = cfg.as_tuple()
    return float(np.exp(abs(np.log2(t[2] * t[3]) - 10)
                        + 0.2 * np.log2(t[4] + t[5] + t[6])
                        + 0.1 * np.log2(t[0] * t[1])))


def _warm_buckets(tuner, *, n_min: int, n_max: int, n_sample: int,
                  filter_steps: int, dkl_steps: int) -> None:
    """Compile the engine's pow2-bucket programs untimed.

    Compile is one-off per process, not throughput (the same policy
    ``mapper_throughput`` applies) — and the engine only HAS O(log n)
    programs to warm.  The scalar loop has no analogue: every growing
    dataset size is a fresh shape, so its per-iteration retraces are the
    measured pathology and stay inside the timed region.
    """
    from repro.core.tuner import _DKL_OPT, _FILTER_OPT
    from repro.runtime import native_kernels
    from repro.engine.tuner_train import (fit_dkl, fit_filter,
                                          score_candidates)
    rng = np.random.default_rng(0)
    fm, sg = tuner.filter_model, tuner.suggestion
    xq = rng.normal(size=(n_sample, 7)).astype(np.float32)
    ok = np.ones(n_sample, bool)
    for b in sorted({pow2_bucket(n) for n in range(n_min, n_max + 1)}):
        x = rng.normal(size=(b, 7)).astype(np.float32)
        y = rng.normal(size=(b,)).astype(np.float32)
        mask = np.zeros(b, bool)
        mask[:max(3, b // 2)] = True
        # the fit entry points donate (params, opt_state); the models keep
        # using theirs afterwards, so the warm-up burns copies
        import jax.numpy as jnp
        from jax import tree_util
        copy = lambda t: tree_util.tree_map(jnp.array, t)  # noqa: E731
        fit_filter(copy(fm.params), copy(fm.opt_state), x, y, mask,
                   opt=_FILTER_OPT, steps=filter_steps)
        fit_dkl(copy(sg.params), copy(sg.opt_state), x, y, mask,
                opt=_DKL_OPT, steps=dkl_steps)
        score_candidates(sg.params, x, y, mask, xq, ok, tuner.beta,
                         use_pallas=native_kernels())


def _drive(backend: str, cfgs, areas, costs, *, iterations: int, n0: int,
           grow: int, n_sample: int, propose_k: int, filter_steps: int,
           dkl_steps: int, seed: int):
    """One growing-dataset DSE schedule; returns per-iteration (time, n_obs).

    ``grow=1`` mirrors the paper's Fig. 7 first-legal-only walk: each DSE
    iteration maps one architecture and feeds one observation back.  The
    engine's pow2-bucket programs are warmed untimed (see
    :func:`_warm_buckets`); the loop backend's per-iteration retraces — a
    fresh XLA program per dataset size — stay timed, because no warm-up can
    exist for shapes that never repeat.
    """
    tuner = PimTuner(seed=seed, n_sample=n_sample, backend=backend)
    feed = 0
    for _ in range(n0):
        tuner.observe(cfgs[feed], areas[feed], costs[feed])
        feed += 1
    if backend == "scan":
        _warm_buckets(tuner, n_min=n0, n_max=n0 + grow * iterations,
                      n_sample=n_sample, filter_steps=filter_steps,
                      dkl_steps=dkl_steps)
    # warm-up at the starting size: compile + one propose
    tuner.filter_model.fit(filter_steps)
    tuner.suggestion.fit(dkl_steps)
    tuner.propose(propose_k)
    times, n_obs = [], []
    for _ in range(iterations):
        for _ in range(grow):
            tuner.observe(cfgs[feed], areas[feed], costs[feed])
            feed += 1
        t0 = time.perf_counter()
        tuner.filter_model.fit(filter_steps)
        tuner.suggestion.fit(dkl_steps)
        tuner.propose(propose_k)
        times.append(time.perf_counter() - t0)
        n_obs.append(feed)
    return np.array(times), np.array(n_obs)


# the one CI smoke contract, shared by `--smoke` and `benchmarks.run --fast`:
# short schedule, soft 1.5x threshold (the full run enforces 5x); the pow2
# program-count bound is asserted in both modes
SMOKE_KW = dict(iterations=10, n0=24, grow=2, n_sample=256, filter_steps=60,
                dkl_steps=80, min_speedup=1.5)


def run(iterations: int = 40, n0: int = 16, grow: int = 1,
        n_sample: int = 2048, propose_k: int = 8, filter_steps: int = 200,
        dkl_steps: int = 300, seed: int = 0, min_speedup: float = 5.0,
        assert_5x: bool = True, min_obs: int = 30) -> list[dict]:
    rng = np.random.default_rng(seed)
    cfgs = sample_configs_batch(n0 + grow * iterations + 8, rng)
    areas = [c.area_mm2() for c in cfgs]
    costs = [_synthetic_cost(c) for c in cfgs]
    kw = dict(iterations=iterations, n0=n0, grow=grow, n_sample=n_sample,
              propose_k=propose_k, filter_steps=filter_steps,
              dkl_steps=dkl_steps, seed=seed)

    pc0 = compiled_program_count()
    eng_t, n_obs = _drive("scan", cfgs, areas, costs, **kw)
    pc1 = compiled_program_count()
    loop_t, _ = _drive("loop", cfgs, areas, costs, **kw)

    n_final = int(n_obs[-1])
    asserted = ("fit_filter", "fit_dkl", "score_candidates")
    unavailable = [k for k in asserted
                   if pc0.get(k, -1) < 0 or pc1.get(k, -1) < 0]
    # the bound must fail loudly, not vacuously: if a jax upgrade drops the
    # cache introspection, the contract can no longer be checked
    assert not unavailable, (
        f"jit cache introspection unavailable for {unavailable} — the "
        f"pow2 program-count contract cannot be verified on this jax")
    programs = {k: pc1[k] - pc0[k] for k in pc1
                if pc0[k] >= 0 and pc1[k] >= 0}
    program_bound = int(math.log2(pow2_bucket(n_final))) + 2
    for name in asserted:
        got = programs[name]
        assert got <= program_bound, (
            f"{name} compiled {got} XLA programs over a {iterations}-"
            f"iteration run (pow2-bucket bound: {program_bound} at "
            f"{n_final} observations) — the shape bucketing regressed")

    at = n_obs >= min_obs
    assert at.any(), f"schedule never reached {min_obs} observations"
    eng_s = float(eng_t[at].sum())
    loop_s = float(loop_t[at].sum())
    speedup = loop_s / eng_s
    if assert_5x:
        assert speedup >= min_speedup, (
            f"engine tuner only {speedup:.2f}x faster than the scalar loop "
            f"at >={min_obs} observations (contract: >={min_speedup}x)")
    n_at = int(at.sum())
    return [{
        "table": "tuner", "iterations": iterations, "n_obs_final": n_final,
        "n_sample": n_sample, "min_obs": min_obs,
        "loop_s": loop_s, "engine_s": eng_s,
        "loop_iters_per_s": n_at / loop_s,
        "engine_iters_per_s": n_at / eng_s,
        "loop_total_s": float(loop_t.sum()),
        "engine_total_s": float(eng_t.sum()),
        "speedup": speedup,
        "programs": programs, "program_bound": program_bound,
    }]


def main(smoke: bool = False) -> None:
    r = run(**SMOKE_KW)[0] if smoke else run()[0]
    print(f"tuner_loop,{1e6 / r['loop_iters_per_s']:.1f},"
          f"iters_per_s={r['loop_iters_per_s']:.2f}")
    print(f"tuner_engine,{1e6 / r['engine_iters_per_s']:.1f},"
          f"iters_per_s={r['engine_iters_per_s']:.2f} "
          f"speedup={r['speedup']:.1f}x "
          f"programs={sum(r['programs'].values())} "
          f"(bound {r['program_bound']}/fn at {r['n_obs_final']} obs)")


if __name__ == "__main__":
    main(smoke="--smoke" in sys.argv)
