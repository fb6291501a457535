"""Device-resident DSE iteration pipeline (the fused Fig. 7 hot path).

``run_dse``'s staged path round-trips through the host between every tuner
stage of an iteration: the filter model's predicted areas are pulled back
and exponentiated in numpy, the suggestion model's scores come back as a
numpy array for ``np.argsort``, the dedup-to-k walk runs over Python
tuples, and each ``fit`` blocks on ``float(losses[-1])`` before the next
iteration starts.  :class:`DsePipeline` chains the SAME jitted stage
functions — the filter forward pass, the fused
:func:`repro.engine.tuner_train.score_candidates` dispatch, and an
in-array top-k selection replicating
:func:`repro.core.hardware.configs_from_rows` — with device arrays flowing
between them:

* every stage input is an explicit ``jax.device_put`` (no implicit
  host->device transfers; ``tests/test_pipeline.py`` pins this under
  ``jax.transfer_guard("disallow")``),
* the area mask, candidate scores, stable sort, stop-at-first-invalid
  walk, duplicate suppression, and top-k scatter all stay on device,
* exactly ONE host sync per proposal — the ``device_get`` of the winner
  indices — after which the k ``HwConfig`` objects materialize from the
  host-side sample matrix, and
* :meth:`fit` uses the models' ``fit_arrays`` hooks, so both Adam
  trajectories are enqueued asynchronously and the host never blocks on a
  loss scalar (the staged path syncs twice per iteration here).

Selection semantics are bit-compatible with the staged path: the same
sampled value matrix (identical RNG stream), the same jitted scoring
program, a stable argsort, and a walk that stops at the first
area-rejected row — so a shared seed yields identical proposals, pinned by
the parity tests and the ``benchmarks/pipeline_throughput.py`` contract.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..core.hardware import (HwConfig, normalize_params_batch,
                             sample_config_values)
from ..obs import trace
from ..runtime import native_kernels
from .jit_registry import register_jits
from .tuner_train import mlp_forward, score_candidates


@jax.jit
def _area_mask(params, xq, budget):
    """Filter-model area mask with the all-reject fallback folded in-array.

    Mirrors the staged ``FilterModel.predict_area_x`` + budget comparison
    (same MLP forward, same ``exp(pred) * budget <= budget`` test) and the
    staged propose's "an all-reject filter would starve the search" escape:
    when no candidate passes, every candidate does.
    """
    pred = mlp_forward(params, xq)[:, 0]
    mask = jnp.exp(pred) * budget <= budget
    return jnp.where(jnp.any(mask), mask, True)


@jax.jit
def _masked_zeros(ok):
    """Scores for an untrained suggestion model: zeros, masked to +inf."""
    return jnp.where(ok, jnp.zeros(ok.shape, jnp.float32), jnp.inf)


# jitted so the trajectory's last loss is picked on device: eager indexing
# (even a static a[-1:]) dispatches dynamic_slice with a host index scalar,
# which a transfer guard rejects
_last = jax.jit(lambda a: a[-1])


@partial(jax.jit, static_argnames=("k",))
def _select_topk(vals, scores, valid, *, k: int):
    """In-array twin of :func:`repro.core.hardware.configs_from_rows`.

    Stable-sorts the candidate rows by score, walks them best-first
    stopping at the first invalid row (``cumprod`` over the sorted mask),
    suppresses rows whose exact value tuple already appeared earlier in
    the walk (pairwise-equality against the strict lower triangle), and
    scatters the first ``k`` survivors' ORIGINAL row indices into rank
    order.  Returns ``(indices [k], count)``; unfilled slots are -1.
    """
    order = jnp.argsort(scores)             # stable, like np kind="stable"
    v = vals[order]
    alive = jnp.cumprod(valid[order].astype(jnp.int32)).astype(bool)
    dup = jnp.tril(jnp.all(v[:, None, :] == v[None, :, :], axis=-1),
                   -1).any(axis=1)
    keep = alive & ~dup
    rank = jnp.cumsum(keep.astype(jnp.int32)) - 1
    take = keep & (rank < k)
    # ranks of taken rows are unique and < k; everything else piles into
    # the sacrificial slot k, which the trim below discards
    slot = jnp.where(take, rank, k)
    sel = jnp.full((k + 1,), -1, jnp.int32).at[slot].set(
        order.astype(jnp.int32))
    return sel[:k], jnp.sum(take.astype(jnp.int32))


#: module-level jit objects, keyed for ``compiled_program_count``-style
#: introspection (see :func:`repro.engine.engine_program_counts`),
#: registered at creation time
_JITTED = register_jits(
    area_mask=_area_mask,
    masked_zeros=_masked_zeros,
    last=_last,
    select_topk=_select_topk,
)


class ProposalHandle:
    """An in-flight fused propose: device winners, resolvable late.

    ``run_dse``'s double-buffered pipeline holds one of these across the
    iteration boundary — the propose chain dispatched at iteration ``k``'s
    ingest tail resolves (one small ``device_get``) at the top of
    iteration ``k+1``.
    """

    __slots__ = ("_vals", "_dev", "_cons", "_props")

    def __init__(self, vals, dev: dict, cons):
        self._vals = vals
        self._dev = dev
        self._cons = cons
        self._props: list[HwConfig] | None = None

    def resolve(self) -> list[HwConfig]:
        """Block on the winner indices and materialize the HwConfigs."""
        if self._props is None:
            with trace.span("propose_resolve", cat="engine") as sp:
                got = jax.device_get(self._dev)
                sel, cnt = got["sel"], int(got["cnt"])
                sp["selected"] = cnt
                if "mask_legal" in got:   # sharded wave stats ride along
                    sp["mask_legal"] = int(got["mask_legal"])
                    sp["best_score"] = float(got["best_score"])
            self._props = [
                HwConfig.from_tuple(tuple(int(x) for x in self._vals[i]),
                                    cons=self._cons)
                for i in sel[:cnt]]
            self._vals = self._dev = None
        return self._props


class DsePipeline:
    """Strategy adapter running a scan-backend :class:`PimTuner` fused.

    Drop-in for the tuner anywhere ``run_dse`` accepts a strategy (or pass
    ``run_dse(..., pipeline=True)`` to wrap transparently): ``propose`` is
    the device-resident chain above, ``observe`` delegates, and ``fit``
    defers the loss sync.  The evaluator side of the iteration batches its
    scheduler work through ``prefill_schedules_many`` when the evaluator's
    ``batch_prefill`` flag is on (``run_dse(pipeline=True)`` enables it for
    the duration of the run).
    """

    def __init__(self, tuner):
        missing = [a for a in ("filter_model", "suggestion", "rng",
                               "n_sample", "cons")
                   if not hasattr(tuner, a)]
        if missing:
            raise ValueError(f"DsePipeline needs a PimTuner-like strategy; "
                             f"{type(tuner).__name__} lacks {missing}")
        if getattr(tuner, "backend", None) != "scan":
            raise ValueError("DsePipeline requires a scan-backend tuner "
                             f"(got backend={getattr(tuner, 'backend', None)!r})")
        self.tuner = tuner
        self.name = getattr(tuner, "name", "nicepim")
        # the fused Pallas LCB kernel where it compiles natively; the jnp
        # scoring elsewhere (interpret-mode Pallas is only a test path)
        self._use_pallas = native_kernels()
        # scalars/constants the jitted stages consume, pre-staged once so
        # steady-state proposals perform no implicit host->device transfer
        self._beta = jax.device_put(np.float32(tuner.suggestion.beta))
        self._budget = jax.device_put(
            np.float32(tuner.cons.area_budget_mm2))
        self._ones = self._put_rows(np.ones(tuner.n_sample, bool))

    def _put_rows(self, x):
        """Host->device placement for ``[n_sample, ...]`` row arrays.

        The sharded campaign runner (:mod:`repro.engine.sharded`) overrides
        this with a config-axis :class:`~jax.sharding.NamedSharding` put;
        the row-local stage math is placement-independent, so overriding
        placement alone keeps proposals bitwise identical.
        """
        return jax.device_put(x)

    # -- the fused propose chain -------------------------------------------

    def propose_dispatch(self, k: int = 8) -> ProposalHandle:
        """Enqueue the fused propose chain; NO host sync happens here.

        Returns a :class:`ProposalHandle` whose ``resolve()`` performs the
        iteration's one ``device_get`` (k winner indices + a count) —
        callers choose when to pay it, so the chain's compute can hide
        under unrelated host work.
        """
        t = self.tuner
        with trace.span("fused_propose", cat="engine",
                        n=t.n_sample, k=k):
            # stage 0 (host): vectorized draw + normalize, then ONE put
            vals = sample_config_values(t.n_sample, t.rng, t.cons)
            xq = self._put_rows(normalize_params_batch(vals))
            ok = (_area_mask(t.filter_model.params, xq, self._budget)
                  if t.filter_model.trained() else self._ones)
            scores = self._scores(xq, ok)
            sel, cnt = _select_topk(self._put_rows(vals), scores, ok, k=k)
        return ProposalHandle(vals, {"sel": sel, "cnt": cnt}, t.cons)

    def propose(self, k: int = 8) -> list[HwConfig]:
        return self.propose_dispatch(k).resolve()

    def _scores(self, xq, ok):
        sg = self.tuner.suggestion
        if len(sg._y) < 3:
            return _masked_zeros(ok)
        if sg._dirty or sg._train is None:
            sg.fit_arrays()          # same refit-when-stale rule as rank_x
        xp, yp, mask = sg._train
        return score_candidates(sg.params, xp, yp, mask, xq, ok,
                                self._beta, use_pallas=self._use_pallas)

    # -- the strategy protocol ---------------------------------------------

    def observe(self, cfg: HwConfig, area_mm2: float,
                cost: float | None) -> None:
        self.tuner.observe(cfg, area_mm2, cost)

    def fit(self) -> dict:
        """Refit both models WITHOUT blocking on their losses.

        Returns device scalars (or NaN before the models have enough
        observations); ``run_dse`` only formats them under ``verbose``, so
        the non-verbose loop never waits for a fit to finish — the next
        iteration's host-side sampling and mapper work overlap with the
        enqueued Adam scans.
        """
        nan = float("nan")
        fl = self.tuner.filter_model.fit_arrays()
        dl = self.tuner.suggestion.fit_arrays()
        return {"filter": nan if fl is None else _last(fl),
                "dkl": nan if dl is None else _last(dl)}
