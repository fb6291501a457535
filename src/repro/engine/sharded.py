"""Sharded mega-campaigns: many tenants, one mesh, one shared eval table.

ROADMAP item 1's "DSE-as-a-service" runner.  A *tenant* is one DSE stream
— a (workloads, strategy, seed, constraints) tuple, exactly what
``run_dse`` runs single-stream — and a :class:`ShardedCampaign` drives many
of them against shared infrastructure:

* **config-axis sharding** — :class:`ShardedProposer` re-places the fused
  propose chain's ``[n_sample, ...]`` candidate rows with a
  ``NamedSharding`` over a 1-D ``config`` device mesh
  (:func:`campaign_mesh`, built on :func:`repro.distributed.shardings.
  make_mesh`; ``--xla_force_host_platform_device_count`` makes it
  CPU-testable).  The jitted stages — area mask, fused candidate scoring,
  in-array top-k — are row-local, so they split across the mesh (GSPMD
  for the jnp stages, an explicit ``shard_map`` for the scoring, whose
  Pallas kernel XLA cannot partition) and the proposals stay BITWISE
  identical to the single-device pipeline (pinned by
  ``tests/test_sharded.py``); per-wave legality stats reduce on device
  through a ``shard_map`` kernel too.

* **async wave overlap** — the run loop is a bounded producer/consumer:
  the main thread proposes/ingests/fits (per-tenant sequential semantics,
  which is what keeps each tenant's observation stream identical to its
  single-stream run) while up to ``queue_depth`` waves of mapper/scheduler
  evaluation are in flight on executor threads.  Tenant A's wave N+1
  propose overlaps tenant B's wave N mapping; ``jax.block_until_ready``
  happens only at tenant-completion observation boundaries.

* **persistent shared cache** — hand the campaign a
  :class:`repro.engine.cache.PersistentEvalCache` and every evaluation is
  one durable sqlite commit: concurrent eval workers, killed-and-resumed
  campaigns, and repeated submissions of the same tenant all dedupe
  against one content-addressed table (``benchmarks/campaign_throughput``
  gates the resulting >=2x wall-clock and the zero-re-evaluation resume).

Checkpoint/resume mirrors :class:`repro.engine.campaign.Campaign`'s file
format (JSON observations per tenant behind a campaign fingerprint), but
recovery is *replay-by-re-proposal*: a resumed tenant re-drives its whole
wave sequence from iteration 0.  Every strategy here is deterministic
given its seed, so the re-run proposes the exact configs of the original
run; with a shared :class:`PersistentEvalCache` each already-evaluated
point is served from the durable table (the mapper never re-runs —
``reeval_preexisting`` stays 0) and the continued stream is BITWISE
identical to an uninterrupted run, not merely statistically equivalent.
The re-run pays only the cheap propose/fit host work per completed wave.
"""

from __future__ import annotations

import json
import math
import os
import time
import warnings
from collections import deque
from concurrent.futures import FIRST_COMPLETED, ThreadPoolExecutor, wait
from contextlib import nullcontext
from dataclasses import dataclass, field
from pathlib import Path
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.dse import (DseResult, Observation, WorkloadEvaluator,
                        ingest_results, propose_screen)
from ..core.hardware import (DEFAULT_CONSTRAINTS, HwConfig, PimConstraints,
                             normalize_params_batch, sample_config_values)
from ..core.ir import DnnGraph
from ..core.surrogates import make_strategy
from ..obs import metrics as obs_metrics
from ..obs import trace
from ..obs.metrics import collect_engine_metrics
from .batch_cost import batch_area_mm2
from .cache import EvalCache, _sha, cons_digest, workloads_digest
from .campaign import CampaignResult, _obs_from_json, _obs_to_json
from .pareto import ParetoFront
from .jit_registry import register_jit
from .pipeline import (DsePipeline, ProposalHandle, _area_mask,
                       _masked_zeros, _select_topk)
from .tuner_train import _score_candidates_jit, score_candidates

#: module jit registry (PIM002 / ``engine_program_counts`` contract).  The
#: shard_map programs close over a concrete mesh, so they are built lazily
#: per mesh and registered here as ``<name>[<ndev>]``.
_JITTED: dict = {}

_PER_MESH: dict = {}


def _per_mesh(name: str, mesh, build):
    """The ``name`` program of ``mesh``, built once by ``build()``."""
    fn = _PER_MESH.get((name, mesh))
    if fn is None:
        if len(_PER_MESH) >= 16:   # bounded: meshes are few
            _PER_MESH.clear()
        fn = _PER_MESH[(name, mesh)] = build()
        register_jit(_JITTED, f"{name}[{mesh.devices.size}]", fn)
    return fn


# --------------------------------------------------------------------------
# mesh + row placement
# --------------------------------------------------------------------------

def campaign_mesh(n_devices: int | None = None):
    """A 1-D ``config`` mesh over (a prefix of) the host's devices.

    On CPU, set ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    *before* the first jax import to get an N-device mesh for tests and
    benchmarks (the same trick ``launch/mesh.py`` documents).
    """
    from ..distributed.shardings import make_mesh
    n = n_devices or len(jax.devices())
    return make_mesh((n,), ("config",))


def shard_config_rows(mesh, x):
    """``device_put`` a ``[rows, ...]`` array row-sharded over ``config``.

    Falls back to mesh-wide replication when the device count does not
    divide the row count (divisibility-guarded like every rule in
    ``distributed/shardings.py``) — results are placement-independent
    either way, only the partitioning changes.
    """
    x = np.asarray(x)
    ndev = mesh.devices.size
    spec = P("config") if ndev > 1 and x.shape[0] % ndev == 0 else P()
    return jax.device_put(x, NamedSharding(mesh, spec))


def _wave_stats_for(mesh):
    """Per-mesh ``shard_map`` kernel reducing wave legality stats on device.

    Each device reduces its own row block, then ``psum``/``pmin`` combine
    across the ``config`` axis — both order-independent, so the stats are
    deterministic under any device count.
    """
    def _stats(scores, ok):
        legal = jax.lax.psum(jnp.sum(ok.astype(jnp.int32)), "config")
        best = jax.lax.pmin(
            jnp.min(jnp.where(ok, scores, jnp.inf)), "config")
        return legal, best
    return _per_mesh("wave_stats", mesh, lambda: jax.jit(jax.shard_map(
        _stats, mesh=mesh, in_specs=(P("config"), P("config")),
        out_specs=(P(), P()))))


def _scores_for(mesh, use_pallas: bool):
    """Per-mesh ``shard_map`` of the fused candidate scoring.

    Each device scores its own row block against the replicated model and
    training set.  XLA cannot partition a Pallas kernel by itself, so the
    native ``lcb_rows`` path needs the explicit per-device split; the jnp
    path takes the same one, and scoring stays row-local either way.
    ``check_vma`` is off because a Pallas call declares no per-axis
    variance for its output.
    """
    def _score(params, xt, yt, mask, xq, ok, beta):
        return _score_candidates_jit(params, xt, yt, mask, xq, ok, beta,
                                     use_pallas=use_pallas)
    name = "scores_pallas" if use_pallas else "scores"
    return _per_mesh(name, mesh, lambda: jax.jit(jax.shard_map(
        _score, mesh=mesh, in_specs=(P(),) * 4 + (P("config"),) * 2 + (P(),),
        out_specs=P("config"), check_vma=False)))


# --------------------------------------------------------------------------
# the sharded propose chain
# --------------------------------------------------------------------------

class ShardedProposer(DsePipeline):
    """:class:`DsePipeline` with candidate rows sharded over a mesh.

    Same RNG stream, same jitted stage programs, same selection walk — the
    ONLY change is placement: candidate row arrays enter the chain sharded
    ``P("config")`` and the model/train-set arrays enter replicated, so
    the row-local stage math splits across the mesh.  Proposals
    are bitwise identical to the base pipeline (row-local elementwise ops
    and matmul rows don't change under partitioning; the top-k sort sees
    identical scores), which is what lets a sharded campaign share one
    observation stream with its single-stream twin.
    """

    def __init__(self, tuner, mesh=None):
        self.mesh = mesh if mesh is not None else campaign_mesh()
        self._rep = NamedSharding(self.mesh, P())
        super().__init__(tuner)
        # jit-closure scalars replicate on the mesh (super() committed them
        # to the default device, which a sharded jit would reject)
        self._beta = jax.device_put(np.float32(tuner.suggestion.beta),
                                    self._rep)
        self._budget = jax.device_put(
            np.float32(tuner.cons.area_budget_mm2), self._rep)
        self._wave_stats = _wave_stats_for(self.mesh)
        self._sharded = (self.mesh.devices.size > 1
                         and tuner.n_sample % self.mesh.devices.size == 0)

    def _put_rows(self, x):
        return shard_config_rows(self.mesh, x)

    def _replicate(self, tree):
        """Mesh-replicate a (possibly committed single-device) pytree."""
        return jax.tree.map(lambda a: jax.device_put(a, self._rep), tree)

    def propose_dispatch(self, k: int = 8) -> ProposalHandle:
        """Sharded fused-propose dispatch: winner indices and the
        device-reduced legality stats ride one handle, so the wave still
        pays exactly one host sync — at ``resolve()`` time."""
        t = self.tuner
        with trace.span("fused_propose", cat="engine", n=t.n_sample, k=k,
                        devices=self.mesh.devices.size):
            vals = sample_config_values(t.n_sample, t.rng, t.cons)
            xq = self._put_rows(normalize_params_batch(vals))
            ok = (_area_mask(self._replicate(t.filter_model.params), xq,
                             self._budget)
                  if t.filter_model.trained() else self._ones)
            scores = self._scores(xq, ok)
            sel, cnt = _select_topk(self._put_rows(vals), scores, ok, k=k)
            dev = {"sel": sel, "cnt": cnt}
            if self._sharded:
                legal, best = self._wave_stats(scores, ok)
                dev["mask_legal"], dev["best_score"] = legal, best
        return ProposalHandle(vals, dev, t.cons)

    def _scores(self, xq, ok):
        sg = self.tuner.suggestion
        if len(sg._y) < 3:
            return _masked_zeros(ok)
        if sg._dirty or sg._train is None:
            sg.fit_arrays()
        xp, yp, mask = self._replicate(sg._train)
        params = self._replicate(sg.params)
        if not self._sharded:
            return score_candidates(params, xp, yp, mask, xq, ok,
                                    self._beta, use_pallas=self._use_pallas)
        with trace.span("score_candidates", cat="engine",
                        bucket=int(yp.shape[0]), candidates=int(xq.shape[0]),
                        devices=self.mesh.devices.size):
            return _scores_for(self.mesh, self._use_pallas)(
                params, xp, yp, mask, xq, ok, self._beta)


# --------------------------------------------------------------------------
# tenants
# --------------------------------------------------------------------------

@dataclass
class TenantSpec:
    """One DSE stream of a mega-campaign (the unit ``run_dse`` runs solo).

    ``name`` keys checkpoints and results, so it must be unique within the
    campaign.  Two specs with identical search parameters and workloads
    (e.g. a nightly resubmission) produce identical observation streams —
    the shared persistent cache then serves the repeat entirely from disk.
    """

    name: str
    workloads: Sequence[DnnGraph]
    strategy: str = "nicepim"
    seed: int = 0
    iterations: int = 8
    propose_k: int = 4
    n_sample: int = 256
    cons: PimConstraints = DEFAULT_CONSTRAINTS
    evaluate_all_legal: bool = False
    evaluator_kwargs: dict = field(default_factory=dict)
    strategy_kwargs: dict = field(default_factory=dict)

    def fingerprint(self) -> dict:
        return {
            "workloads": workloads_digest(self.workloads),
            "cons": cons_digest(self.cons),
            "strategy": self.strategy, "seed": self.seed,
            "iterations": self.iterations, "propose_k": self.propose_k,
            "n_sample": self.n_sample,
            "evaluate_all_legal": self.evaluate_all_legal,
            "evaluator_kwargs": repr(sorted(self.evaluator_kwargs.items())),
            "strategy_kwargs": repr(sorted(self.strategy_kwargs.items())),
        }


@dataclass
class _TenantState:
    spec: TenantSpec
    strategy: object
    evaluator: WorkloadEvaluator
    it: int = 0
    obs: list = field(default_factory=list)
    resumed: bool = False
    active_s: float = 0.0
    t_start: float = 0.0
    wall_s: float = 0.0

    @property
    def done(self) -> bool:
        return self.it >= self.spec.iterations


@dataclass
class _Wave:
    it: int
    props: list
    it_obs: list
    to_eval: list
    legal_n: int
    t0: float


# --------------------------------------------------------------------------
# the campaign runner
# --------------------------------------------------------------------------

class ShardedCampaign:
    """Run many tenant DSE streams overlapped on one mesh + shared cache.

    The main thread owns every strategy (propose / observe / fit — the
    per-tenant sequential order that pins parity with single-stream runs);
    ``eval_workers`` executor threads own the mapper/scheduler waves; at
    most ``queue_depth`` waves are in flight.  ``cache`` is shared by every
    tenant's evaluator — pass a :class:`PersistentEvalCache` for the
    cross-process / kill-and-resume dedup story.

    Each worker's ``evaluate_batch`` additionally runs the per-tenant
    overlapped executor (:class:`repro.engine.overlap.OverlapExecutor`):
    within a wave, one workload's scheduling/accounting runs while the
    next workload's candidate costs are in flight.  The executor is
    per-call and the serial-dispatch flag is thread-local, so per-tenant
    overlap composes with the cross-tenant wave loop with no shared state
    beyond the already-locked mapper memos.

    Worker loss: evaluation results only enter tenant state on the main
    thread, so a lost eval worker (or a whole lost process — see the
    kill-and-resume benchmark) costs at most the in-flight waves; every
    completed evaluation is already durable in the persistent cache and is
    served from it on resume, never re-mapped.
    """

    def __init__(self, tenants: Sequence[TenantSpec], *,
                 mesh=None, cache: EvalCache | None = None,
                 queue_depth: int = 2, eval_workers: int | None = None,
                 checkpoint: str | Path | None = None,
                 checkpoint_every_waves: int = 1,
                 pipeline: bool = True,
                 tracer: trace.Tracer | None = None,
                 metrics: obs_metrics.MetricsRegistry | None = None,
                 verbose: bool = False):
        names = [t.name for t in tenants]
        if len(set(names)) != len(names):
            raise ValueError(f"tenant names must be unique: {names}")
        if queue_depth < 1:
            raise ValueError("queue_depth must be >= 1")
        if checkpoint_every_waves < 1:
            raise ValueError("checkpoint_every_waves must be >= 1")
        self.tenants = list(tenants)
        self.mesh = mesh if mesh is not None else campaign_mesh()
        self.cache = cache if cache is not None else EvalCache()
        self.queue_depth = queue_depth
        self.eval_workers = eval_workers or min(4, queue_depth)
        self.checkpoint = Path(checkpoint) if checkpoint else None
        self.checkpoint_every_waves = checkpoint_every_waves
        self.pipeline = pipeline
        self.tracer = tracer
        self.metrics = metrics if metrics is not None else obs_metrics.METRICS
        self.verbose = verbose
        self.pareto = ParetoFront()
        self._waves_since_ckpt = 0
        self._states: list[_TenantState] = []

    # -- checkpoint I/O ----------------------------------------------------

    def _fingerprint(self) -> str:
        # queue_depth / eval_workers / mesh size are deliberately NOT part
        # of the fingerprint: they change scheduling, not any tenant's
        # observation stream, so a checkpoint resumes across them
        return _sha([t.fingerprint() for t in self.tenants])

    def _discard_checkpoint(self, reason: str, detail: str) -> None:
        warnings.warn(
            f"discarding sharded-campaign checkpoint {self.checkpoint} "
            f"({reason}): {detail}; starting fresh",
            RuntimeWarning, stacklevel=3)
        self.metrics.counter("campaign.checkpoint_discarded").inc()
        self.metrics.counter(f"campaign.checkpoint_discarded.{reason}").inc()
        trace.instant("checkpoint_discarded", cat="campaign",
                      reason=reason, path=str(self.checkpoint))

    def _load_checkpoint(self) -> dict[str, list[Observation]]:
        if not self.checkpoint or not self.checkpoint.exists():
            return {}
        try:
            state = json.loads(self.checkpoint.read_text())
        except (json.JSONDecodeError, OSError) as e:
            self._discard_checkpoint("unreadable", str(e))
            return {}
        if state.get("fingerprint") != self._fingerprint():
            self._discard_checkpoint(
                "fingerprint_mismatch",
                "checkpoint was written by a campaign with different "
                "tenants, workloads, constraints or parameters")
            return {}
        cons = {t.name: t.cons for t in self.tenants}
        return {name: [_obs_from_json(d, cons[name]) for d in rows]
                for name, rows in state.get("tenants", {}).items()
                if name in cons}

    def _write_checkpoint(self) -> None:
        if not self.checkpoint:
            return
        with trace.span("checkpoint", cat="campaign") as sp:
            state = {
                "fingerprint": self._fingerprint(),
                "tenants": {s.spec.name: [_obs_to_json(o) for o in s.obs]
                            for s in self._states},
            }
            tmp = self.checkpoint.with_suffix(".tmp")
            tmp.write_text(json.dumps(state))
            os.replace(tmp, self.checkpoint)
            sp["observations"] = sum(len(s.obs) for s in self._states)

    def _maybe_checkpoint(self) -> None:
        self._waves_since_ckpt += 1
        if self._waves_since_ckpt >= self.checkpoint_every_waves:
            self._waves_since_ckpt = 0
            self._write_checkpoint()

    # -- tenant setup ------------------------------------------------------

    def _make_strategy(self, spec: TenantSpec):
        strat = make_strategy(spec.strategy, cons=spec.cons, seed=spec.seed,
                              n_sample=spec.n_sample, **spec.strategy_kwargs)
        tuner_like = all(hasattr(strat, a) for a in
                         ("filter_model", "suggestion", "rng", "n_sample",
                          "cons")) and getattr(strat, "backend",
                                               None) == "scan"
        if self.pipeline and tuner_like:
            return ShardedProposer(strat, self.mesh), True
        return strat, False

    def _tenant_state(self, spec: TenantSpec,
                      saved: list[Observation]) -> _TenantState:
        strat, piped = self._make_strategy(spec)
        kw = dict(spec.evaluator_kwargs)
        kw.setdefault("clear_caches_between_configs", True)
        if piped:
            kw.setdefault("batch_prefill", True)
        ev = WorkloadEvaluator(list(spec.workloads), cache=self.cache, **kw)
        # replay-by-re-proposal: a resumed tenant restarts at iteration 0
        # and re-drives every wave.  Its seeded strategy re-proposes the
        # exact configs of the interrupted run, the shared cache serves
        # their evaluations (persistent table: zero re-mapping), and the
        # continued stream comes out bitwise identical — feeding the saved
        # observations into a fresh model instead would leave the RNG
        # stream behind by the replayed waves' draws and fork the tail
        if saved:
            trace.instant("tenant_resumed", cat="sharded", tenant=spec.name,
                          saved_observations=len(saved))
        return _TenantState(spec=spec, strategy=strat, evaluator=ev,
                            resumed=bool(saved))

    def _offer_pareto(self, obs: list[Observation]) -> None:
        # main-thread only (ingest + replay both run there): no lock needed
        from .pareto import ParetoPoint
        for o in obs:
            if o.cost is None or o.cost != o.cost or math.isinf(o.cost):
                continue
            self.pareto.offer(ParetoPoint(sum(o.latency_s.values()),
                                          sum(o.energy_pj.values()),
                                          o.area_mm2,
                                          payload=list(o.cfg.as_tuple())))

    # -- wave phases -------------------------------------------------------

    def _propose_wave(self, st: _TenantState) -> _Wave:
        spec = st.spec
        t0 = time.time()
        ta = time.perf_counter()
        with trace.span("wave_propose", cat="sharded", tenant=spec.name,
                        it=st.it):
            props, it_obs, to_eval, legal_n = propose_screen(
                st.strategy, st.it, spec.propose_k, spec.cons, spec.name,
                spec.evaluate_all_legal, batch_area_mm2)
        st.active_s += time.perf_counter() - ta
        return _Wave(it=st.it, props=props, it_obs=it_obs, to_eval=to_eval,
                     legal_n=legal_n, t0=t0)

    def _evaluate_wave(self, st: _TenantState, wave: _Wave):
        """Executor-thread phase: map/schedule the wave's legal configs."""
        trace.set_thread_name("eval-worker")
        ta = time.perf_counter()
        with trace.span("wave_evaluate", cat="sharded",
                        tenant=st.spec.name, it=wave.it,
                        configs=len(wave.to_eval)):
            if not wave.to_eval:
                out = []
            elif st.spec.evaluate_all_legal:
                results = st.evaluator.evaluate_batch(
                    [cfg for cfg, _ in wave.to_eval])
                out = [(cfg, area, res) for (cfg, area), res
                       in zip(wave.to_eval, results)]
            else:
                cfg, area = wave.to_eval[0]
                out = [(cfg, area, st.evaluator(cfg))]
        st.active_s += time.perf_counter() - ta
        return out

    def _ingest_wave(self, st: _TenantState, wave: _Wave,
                     evaluated: list) -> None:
        spec = st.spec
        ta = time.perf_counter()
        with trace.span("wave_ingest", cat="sharded", tenant=spec.name,
                        it=wave.it):
            best_gauge = self.metrics.gauge(f"dse.{spec.name}.best_cost")
            legal_hist = self.metrics.histogram(
                f"dse.{spec.name}.legal_fraction")
            ingest_results(st.strategy, wave.it, wave.it_obs, evaluated,
                           self.pareto, spec.name, best_gauge, legal_hist,
                           wave.legal_n, len(wave.props), None,
                           self.verbose, wave.t0)
        st.obs.extend(wave.it_obs)
        st.it = wave.it + 1
        st.active_s += time.perf_counter() - ta
        self._maybe_checkpoint()

    def _finish_tenant(self, st: _TenantState) -> None:
        st.wall_s = time.perf_counter() - st.t_start
        strat = st.strategy
        if isinstance(strat, DsePipeline):
            # tenant-completion observation boundary: drain the deferred
            # Adam fits so the tenant's reported wall time covers its model
            # state (the run loop itself never blocks on a fit)
            t = strat.tuner
            jax.block_until_ready((t.filter_model.params,
                                   t.suggestion.params))
        trace.instant("tenant_done", cat="sharded", tenant=st.spec.name,
                      observations=len(st.obs))

    # -- the run -----------------------------------------------------------

    def run(self) -> CampaignResult:
        ctx = trace.activate(self.tracer) if self.tracer is not None \
            else nullcontext()
        with ctx:
            trace.set_thread_name("sharded-campaign")
            saved = self._load_checkpoint()
            self._states = [self._tenant_state(t, saved.get(t.name, []))
                            for t in self.tenants]
            now = time.perf_counter()
            for s in self._states:
                s.t_start = now
            ready = deque(s for s in self._states if not s.done)
            for s in self._states:
                if s.done:
                    self._finish_tenant(s)
            pending: dict = {}
            with ThreadPoolExecutor(
                    max_workers=self.eval_workers) as pool:
                while ready or pending:
                    # producer: keep up to queue_depth waves in flight —
                    # each tenant has at most one (sequential semantics)
                    while ready and len(pending) < self.queue_depth:
                        st = ready.popleft()
                        wave = self._propose_wave(st)
                        fut = pool.submit(self._evaluate_wave, st, wave)
                        pending[fut] = (st, wave)
                    done, _ = wait(pending, return_when=FIRST_COMPLETED)
                    for fut in done:
                        st, wave = pending.pop(fut)
                        self._ingest_wave(st, wave, fut.result())
                        if st.done:
                            self._finish_tenant(st)
                        else:
                            ready.append(st)
            self._write_checkpoint()
            snapshot = collect_engine_metrics(
                self.metrics, cache=self.cache, pareto=self.pareto)
        return CampaignResult(
            results={s.spec.name: DseResult(s.obs) for s in self._states},
            pareto=self.pareto, cache_stats=dict(self.cache.stats),
            resumed=[s.spec.name for s in self._states if s.resumed],
            timings_s={s.spec.name: s.active_s for s in self._states},
            wall_s={s.spec.name: s.wall_s for s in self._states},
            metrics=snapshot)
