"""The overall NicePIM design-space-exploration loop (Fig. 7).

Per iteration: the strategy (PIM-Tuner or a Fig. 9 comparison strategy)
proposes candidate hardware configurations; candidates are area-checked
one-by-one with the "simulator" (our analytic area model, standing in for
Timeloop+Accelergy) until a legal one is found; the PIM-Mapper +
Data-Scheduler produce mapping schemes for every workload DNN and the
resulting latency/energy feed the cost function

    Cost = sum_DNN Energy^alpha * Latency^beta * gamma      (Eq. 1)

which is appended to the strategy's dataset before its models are refit.
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass, field

from .hardware import DEFAULT_CONSTRAINTS, HwConfig, PimConstraints
from .ir import DnnGraph
from .mapper import PimMapper, clear_mapper_caches, evaluate_mapping
from ..obs import metrics, trace


@dataclass
class Observation:
    iteration: int
    cfg: HwConfig
    area_mm2: float
    legal: bool
    cost: float | None = None
    latency_s: dict = field(default_factory=dict)
    energy_pj: dict = field(default_factory=dict)


@dataclass
class DseResult:
    observations: list[Observation]

    def best_cost_curve(self) -> list[float]:
        best = math.inf
        out = []
        cur_iter = -1
        for o in self.observations:
            if o.cost is not None:
                best = min(best, o.cost)
            if o.iteration != cur_iter:
                cur_iter = o.iteration
                out.append(best)
            else:
                out[-1] = best
        return out

    def quality_curve(self) -> list[float]:
        """Paper Fig. 9 metric: mean reciprocal cost of the best 3 so far."""
        costs: list[float] = []
        out = []
        cur_iter = -1
        for o in self.observations:
            if o.cost is not None:
                costs.append(o.cost)
            if o.iteration != cur_iter:
                cur_iter = o.iteration
                out.append(self._top3(costs))
            else:
                out[-1] = self._top3(costs)
        return out

    @staticmethod
    def _top3(costs: list[float]) -> float:
        if not costs:
            return 0.0
        top = sorted(costs)[:3]
        return sum(1.0 / c for c in top) / len(top)

    def best(self) -> Observation:
        cands = [o for o in self.observations if o.cost is not None]
        return min(cands, key=lambda o: o.cost)


class WorkloadEvaluator:
    """Maps + schedules every workload on a config; caches by config tuple.

    An optional :class:`repro.engine.cache.EvalCache` adds content-addressed
    memoization shared across strategies / processes / checkpoint resumes on
    top of the per-instance tuple cache.

    ``mapper_backend`` selects the PIM-Mapper costing path (``"batched"`` —
    the vectorized engine — or ``"scalar"``); it folds into
    ``mapper_kwargs`` so it also keys the content-addressed cache.
    ``scheduler_backend`` selects the Data-Scheduler's joint-LS path
    (``"scan"`` — the jitted engine search, batched per mapping — or
    ``"loop"``, the host-Python reference); it keys both caches too, since
    the two searches draw different RNG streams.
    ``clear_caches_between_configs=True`` drops the mapper-level memos
    (candidate tables, node costs, Data-Scheduler solves — mostly hw-keyed,
    plus the hw-independent shape memos) after each newly evaluated
    configuration, keeping long multi-config campaigns at a flat memory
    footprint; :meth:`evaluate_batch` clears once per batch instead so the
    shape memos amortize across the whole batch.
    ``batch_prefill=True`` makes :meth:`evaluate_batch` solve the WHOLE
    batch's uncached sharing schedules in one cross-config
    ``prefill_schedules_many`` pass before the per-mapping accounting walk
    (one ``schedule_many`` dispatch per NoC-scalar group instead of one
    per mapping); results are bit-identical either way, so the flag keys
    neither cache.  ``run_dse(..., pipeline=True)`` turns it on for the
    duration of the run.
    ``overlap=True`` (the default) runs :meth:`evaluate_batch` through the
    :class:`repro.engine.overlap.OverlapExecutor`: each workload wave's
    scheduling prefill and accounting walk are deferred into the window
    where the NEXT workload's candidate costs are in flight on device.
    Deferred waves retire strictly FIFO, so cost accumulation order — and
    every float result — matches the serial schedule exactly; the flag
    keys neither cache.  ``overlap=False`` restores sync-at-dispatch
    serial execution (the benchmark baseline).
    """

    def __init__(self, workloads: list[DnnGraph], *, alpha: float = 1.0,
                 beta: float = 1.0, gamma: float = 1.0,
                 mapper_kwargs: dict | None = None, cache=None,
                 mapper_backend: str | None = None,
                 scheduler_backend: str = "scan",
                 clear_caches_between_configs: bool = False,
                 batch_prefill: bool = False, overlap: bool = True):
        self.workloads = workloads
        self.alpha = alpha
        self.beta = beta
        self.gamma = gamma
        self.mapper_kwargs = dict(mapper_kwargs or {})
        if mapper_backend is not None:
            self.mapper_kwargs["backend"] = mapper_backend
        self.scheduler_backend = scheduler_backend
        self.clear_caches_between_configs = clear_caches_between_configs
        self.batch_prefill = batch_prefill
        self.overlap = overlap
        self._cache: dict[tuple, tuple[float, dict, dict]] = {}
        self.cache = cache
        self._wl_digest: str | None = None
        self.evaluations = 0   # mapper runs actually performed

    def _content_key(self, cfg: HwConfig) -> str:
        # hw_digest covers EVERY PimConstraints field alongside the variable
        # tuple (audited: the cons feed the cost model, capacity, and NoC
        # energies), so a config evaluated under different substrate
        # constants can never alias a cached result
        from ..engine.cache import _sha, hw_digest, workloads_digest
        if self._wl_digest is None:
            # the result depends on the cost-function exponents and every
            # mapper knob, not just (hw, workloads) — key them all
            self._wl_digest = _sha({
                "workloads": workloads_digest(self.workloads),
                "alpha": self.alpha, "beta": self.beta, "gamma": self.gamma,
                "mapper_kwargs": repr(sorted(self.mapper_kwargs.items())),
                "scheduler_backend": self.scheduler_backend,
            })
        return hw_digest(cfg) + ":" + self._wl_digest

    def __call__(self, cfg: HwConfig) -> tuple[float, dict, dict]:
        with trace.span("evaluate", configs=1) as sp:
            return self._eval_one(cfg, sp)

    def _eval_one(self, cfg: HwConfig, sp: dict) -> tuple[float, dict, dict]:
        # the constraints are part of the point's identity: two configs with
        # the same variable tuple but different substrate constants (e.g. a
        # different cap_bank_bytes) must never alias one cache entry
        key = (cfg.as_tuple(), cfg.cons)
        if key in self._cache:
            sp["cache"] = "local_hit"
            return self._cache[key]
        ckey = None
        if self.cache is not None:
            ckey = self._content_key(cfg)
            # single-flight: if another evaluator (eval worker, duplicated
            # tenant) is computing this key, block for its commit instead
            # of re-running the mapper
            hit, _ = self.cache.lease(ckey)
            if hit is not None:
                sp["cache"] = "content_hit"
                out = (hit[0], dict(hit[1]), dict(hit[2]))
                self._cache[key] = out
                return out
        sp["cache"] = "miss"
        self.evaluations += 1
        mapper = PimMapper(cfg, **self.mapper_kwargs)
        lats: dict[str, float] = {}
        ens: dict[str, float] = {}
        cost = 0.0
        try:
            for g in self.workloads:
                try:
                    m = mapper.map(g)
                    with trace.span("accounting"):
                        rep = evaluate_mapping(
                            m, scheduler_backend=self.scheduler_backend)
                except RuntimeError:   # capacity-infeasible mapping
                    # earlier workloads' numbers must not leak into the
                    # caches alongside the inf cost: an infeasible config
                    # has no meaningful per-workload latency/energy entries
                    cost, lats, ens = math.inf, {}, {}
                    break
                lats[g.name] = rep.latency_s
                ens[g.name] = rep.energy_pj
                energy_j = rep.energy_pj * 1e-12
                cost += (energy_j ** self.alpha) \
                    * (rep.latency_s ** self.beta) * self.gamma
            out = (cost, lats, ens)
            self._cache[key] = out
            if ckey is not None:
                self.cache.put(ckey, out)
        finally:
            if ckey is not None:
                self.cache.complete(ckey)
            if self.clear_caches_between_configs:
                # the memo entries are keyed by this cfg: nothing carries
                # over to the next configuration, so drop them
                clear_mapper_caches()
        return out

    def evaluate_batch(self, cfgs: list[HwConfig]
                       ) -> list[tuple[float, dict, dict]]:
        """Evaluate several configs, batch-mapping each workload across them.

        Every workload is mapped under all still-feasible configs in one
        :meth:`PimMapper.map_many` pass — the engine's ``[N configs]`` batch
        axis — instead of one candidate-costing sweep per config.  Results
        are identical to per-config ``__call__`` (pinned by the parity tests)
        and feed the same two caches; duplicate configs in the batch are
        evaluated once.  With ``clear_caches_between_configs`` the mapper
        memos are dropped once after the whole batch (clearing inside it
        would defeat the cross-config batching).
        """
        with trace.span("evaluate", configs=len(cfgs)) as sp:
            return self._eval_batch(cfgs, sp)

    def _eval_batch(self, cfgs: list[HwConfig], sp: dict
                    ) -> list[tuple[float, dict, dict]]:
        out: list = [None] * len(cfgs)
        todo: dict[tuple, list[int]] = {}    # cfg tuple -> batch positions
        cfg_of: dict[tuple, HwConfig] = {}
        for i, cfg in enumerate(cfgs):
            key = (cfg.as_tuple(), cfg.cons)
            if key in self._cache:
                out[i] = self._cache[key]
                continue
            if key not in todo and self.cache is not None:
                hit = self.cache.get(self._content_key(cfg))
                if hit is not None:
                    res = (hit[0], dict(hit[1]), dict(hit[2]))
                    self._cache[key] = res
                    out[i] = res
                    continue
            todo.setdefault(key, []).append(i)
            cfg_of.setdefault(key, cfg)
        # single-flight pass: lease every remaining key in sorted content-key
        # order (every concurrent evaluator acquires ascending, so waits can
        # never cycle into a deadlock).  A lease that resolves to a hit means
        # another evaluator just computed it — take the result; the keys we
        # end up owning are mapped below and completed in the finally.
        leased: list[str] = []
        ckey_of: dict[tuple, str] = {}
        if self.cache is not None and todo:
            for k in sorted(todo, key=lambda k: self._content_key(cfg_of[k])):
                ckey = self._content_key(cfg_of[k])
                hit, owner = self.cache.lease(ckey)
                if hit is not None:
                    res = (hit[0], dict(hit[1]), dict(hit[2]))
                    self._cache[k] = res
                    for i in todo[k]:
                        out[i] = res
                    del todo[k]
                    continue
                leased.append(ckey)
                ckey_of[k] = ckey
        sp["evaluated"] = len(todo)
        sp["cached"] = len(cfgs) - sum(len(v) for v in todo.values())
        if not todo:
            return out
        self.evaluations += len(todo)
        mapper = PimMapper(next(iter(cfg_of.values())), **self.mapper_kwargs)
        costs = {k: 0.0 for k in todo}
        lats: dict[tuple, dict] = {k: {} for k in todo}
        ens: dict[tuple, dict] = {k: {} for k in todo}
        live = list(todo)
        from contextlib import nullcontext
        from ..engine.overlap import OverlapExecutor, serial_dispatch
        executor = OverlapExecutor(enabled=self.overlap)
        ctx = nullcontext() if self.overlap else serial_dispatch()
        try:
            with ctx:
                for g in self.workloads:
                    if not live:
                        break
                    # drive this workload's dispatch/resolve phases; at each
                    # in-flight window the executor steps the PREVIOUS
                    # workload's deferred scheduling/accounting — the span
                    # nesting in the trace shows the overlap
                    with trace.span("map_wave", cat="engine", graph=g.name,
                                    configs=len(live)):
                        mappings = executor.drive(mapper.map_many_phases(
                            g, [cfg_of[k] for k in live],
                            on_infeasible="none"))
                    wave = live
                    live = [k for k, m in zip(wave, mappings)
                            if m is not None]
                    executor.defer(self._finish_wave(
                        g, wave, mappings, costs, lats, ens))
                executor.drain()  # observation boundary: everything lands
            for k, positions in todo.items():
                res = (costs[k], lats[k], ens[k])
                self._cache[k] = res
                if self.cache is not None:
                    self.cache.put(ckey_of.get(k) or self._content_key(
                        cfg_of[k]), res)
                for i in positions:
                    out[i] = res
        finally:
            if self.cache is not None:
                for ckey in leased:
                    self.cache.complete(ckey)
            if self.clear_caches_between_configs:
                clear_mapper_caches()
        return out

    def _finish_wave(self, g, wave, mappings, costs, lats, ens):
        """Deferred half of one workload wave: prefill + accounting.

        A generator so the :class:`~repro.engine.overlap.OverlapExecutor`
        can advance it stepwise inside the next wave's in-flight windows.
        The statements are the exact serial tail of the historical
        ``evaluate_batch`` workload loop, in the same order — only the
        scheduling boundary moved, not the arithmetic.
        """
        if self.batch_prefill and self.scheduler_backend == "scan":
            # one cross-config scheduler batch for the whole proposal
            # round, instead of one per surviving mapping
            from .mapper import prefill_schedules_many
            prefill_schedules_many([m for m in mappings if m is not None],
                                   backend=self.scheduler_backend)
            yield
        for k, m in zip(wave, mappings):
            if m is None:          # capacity-infeasible: same containment
                costs[k] = math.inf     # as __call__ — nothing leaks
                lats[k], ens[k] = {}, {}
                continue
            with trace.span("accounting"):
                rep = evaluate_mapping(
                    m, scheduler_backend=self.scheduler_backend)
            lats[k][g.name] = rep.latency_s
            ens[k][g.name] = rep.energy_pj
            energy_j = rep.energy_pj * 1e-12
            costs[k] += (energy_j ** self.alpha) \
                * (rep.latency_s ** self.beta) * self.gamma
            yield


def run_dse(strategy, evaluator: WorkloadEvaluator, *, iterations: int = 20,
            propose_k: int = 8,
            cons: PimConstraints = DEFAULT_CONSTRAINTS,
            verbose: bool = False, pareto=None, start_iteration: int = 0,
            on_iteration=None, evaluate_all_legal: bool = False,
            tracer=None, pipeline: bool = False) -> DseResult:
    """One strategy's DSE loop (Fig. 7).

    The whole proposal batch is area-checked in one vectorized call
    (``engine.batch_cost.batch_area_mm2``) instead of one ``area_mm2()``
    per candidate.  ``pareto`` (anything with ``.offer``) receives a
    latency/energy/area :class:`ParetoPoint` per legal finite observation;
    ``on_iteration(it, new_obs)`` fires after every iteration (campaign
    checkpointing); ``start_iteration`` supports checkpoint resume.

    ``evaluate_all_legal=False`` (default) keeps the paper's Fig. 7-4 walk:
    candidates are taken in proposal order until the first legal one, which
    alone is mapped.  ``evaluate_all_legal=True`` maps EVERY legal proposal
    of the batch through ``evaluator.evaluate_batch`` (one multi-config
    candidate-costing pass) — each iteration then feeds ``propose_k``
    observations to ``strategy.observe`` and the Pareto front instead of at
    most one mapped point, widening the suggestion model's dataset per
    refit at far less than ``propose_k`` times the mapping cost.

    ``tracer`` (a :class:`repro.obs.Tracer`) is installed as the active
    tracer for the run; when one is already active (a campaign installed
    it) every iteration's ``propose``/``evaluate``/``fit`` phases emit
    spans regardless.  Per-iteration best-cost and legal-fraction metrics
    land in the process registry under ``dse.<strategy>``.

    ``pipeline=True`` runs the device-resident iteration pipeline: the
    strategy (a scan-backend :class:`PimTuner`) is wrapped in
    :class:`repro.engine.pipeline.DsePipeline` — fused on-device propose,
    one host sync per proposal, deferred fit — and the evaluator's
    ``batch_prefill`` flag is enabled for the duration so each proposal
    round's sharing schedules solve in one cross-config batch.  The
    candidate waves are double-buffered: iteration ``k+1``'s fused propose
    chain is dispatched right after iteration ``k``'s fit (via
    ``DsePipeline.propose_dispatch``) and resolved — one small device_get
    — at the top of iteration ``k+1``, so the propose compute hides under
    the ingest tail (metrics, checkpoint I/O).  The dispatch point sees
    the exact strategy/RNG state the serial propose would, so streams stay
    identical to the staged path under a shared seed (pinned by
    ``tests/test_pipeline.py`` and ``benchmarks/pipeline_throughput.py``).
    """
    from contextlib import nullcontext
    from ..engine.batch_cost import batch_area_mm2
    prefill_restore = None
    if pipeline:
        from ..engine.pipeline import DsePipeline
        if not isinstance(strategy, DsePipeline):
            strategy = DsePipeline(strategy)
        if hasattr(evaluator, "batch_prefill"):
            prefill_restore = evaluator.batch_prefill
            evaluator.batch_prefill = True
    sname = getattr(strategy, "name", type(strategy).__name__.lower())
    best_gauge = metrics.METRICS.gauge(f"dse.{sname}.best_cost")
    legal_hist = metrics.METRICS.histogram(f"dse.{sname}.legal_fraction")
    obs: list[Observation] = []
    ctx = trace.activate(tracer) if tracer is not None else nullcontext()
    # double-buffered proposes: iteration k+1's fused chain is dispatched
    # at iteration k's ingest tail and resolved here at the loop top; an
    # overlap=False evaluator opts the whole campaign out (serial baseline)
    can_dispatch = (pipeline and hasattr(strategy, "propose_dispatch")
                    and getattr(evaluator, "overlap", True))
    nxt: dict = {"handle": None}
    try:
        with ctx:
            for it in range(start_iteration, iterations):
                handle, nxt["handle"] = nxt["handle"], None
                props = handle.resolve() if handle is not None else None
                propose_next = None
                if can_dispatch and it + 1 < iterations:
                    def propose_next():
                        nxt["handle"] = strategy.propose_dispatch(propose_k)
                obs.extend(_dse_iteration(
                    strategy, evaluator, it, propose_k, cons, verbose,
                    pareto, on_iteration, evaluate_all_legal, sname,
                    best_gauge, legal_hist, batch_area_mm2,
                    props=props, propose_next=propose_next))
    finally:
        if prefill_restore is not None:
            evaluator.batch_prefill = prefill_restore
    return DseResult(obs)


def propose_screen(strategy, it: int, propose_k: int,
                   cons: PimConstraints, sname: str,
                   evaluate_all_legal: bool, batch_area_mm2,
                   props: list | None = None
                   ) -> tuple[list, list[Observation],
                              list[tuple[HwConfig, float]], int]:
    """Iteration phase A: propose a batch and area-screen it.

    Proposals are drawn from the strategy, the whole batch is area-checked
    in one vectorized call, and every area-illegal candidate that the walk
    visits is fed back to the strategy immediately (it trains the filter
    model).  Returns ``(props, it_obs, to_eval, legal_n)`` where
    ``to_eval`` is the ``(cfg, area)`` list still needing mapper
    evaluation: all legal proposals under ``evaluate_all_legal``, at most
    the FIRST legal one otherwise (the paper's Fig. 7-4 walk — later
    illegal candidates are then not observed either).

    Shared by :func:`_dse_iteration` and the sharded campaign runner
    (``repro.engine.sharded``), which evaluates ``to_eval`` out-of-line so
    wave N+1's propose can overlap wave N's mapping.  ``props`` supplies a
    pre-resolved proposal batch (the double-buffered pipeline path) and
    skips the propose call.
    """
    it_obs: list[Observation] = []
    if props is None:
        with trace.span("propose", strategy=sname, k=propose_k):
            props = strategy.propose(propose_k)
    areas = batch_area_mm2(props)
    legal_n = sum(1 for a in areas if float(a) <= cons.area_budget_mm2)
    to_eval: list[tuple[HwConfig, float]] = []
    for cfg, area in zip(props, areas):
        area = float(area)
        if area <= cons.area_budget_mm2:
            to_eval.append((cfg, area))
            if not evaluate_all_legal:
                break
        else:
            strategy.observe(cfg, area, None)
            it_obs.append(Observation(it, cfg, area, False))
    return props, it_obs, to_eval, legal_n


def ingest_results(strategy, it: int, it_obs: list[Observation],
                   evaluated: list[tuple[HwConfig, float, tuple]],
                   pareto, sname: str, best_gauge, legal_hist,
                   legal_n: int, n_props: int, on_iteration, verbose: bool,
                   t0: float, propose_next=None) -> list[Observation]:
    """Iteration phase B: observe mapper results, refit, record metrics.

    ``evaluated`` carries ``(cfg, area, (cost, lats, ens))`` per mapped
    config; ``it_obs`` arrives holding phase A's illegal observations and
    leaves holding the full iteration's.  The fit only runs when something
    was mapped — identical to the historical inline loop.  ``propose_next``
    (pipeline double-buffering) fires right after the fit — the earliest
    point with final strategy state — so the next wave's propose chain is
    in flight while the metrics/checkpoint tail below runs on host.
    """
    for cfg, area, (cost, lats, ens) in evaluated:
        if math.isinf(cost):
            strategy.observe(cfg, area, None)
            it_obs.append(Observation(it, cfg, area, True))
        else:
            strategy.observe(cfg, area, cost)
            it_obs.append(Observation(it, cfg, area, True, cost, lats,
                                      ens))
            if pareto is not None:
                from ..engine.pareto import ParetoPoint
                pareto.offer(ParetoPoint(sum(lats.values()),
                                         sum(ens.values()), area,
                                         payload=list(cfg.as_tuple())))
    if evaluated:
        with trace.span("fit", strategy=sname):
            fit_info = strategy.fit()
    else:
        fit_info = None
    if propose_next is not None:
        propose_next()
    # per-iteration search-progress metrics (read back by campaigns
    # and the fig9/report observability sections)
    metrics.METRICS.counter(f"dse.{sname}.iterations").inc()
    metrics.METRICS.counter(f"dse.{sname}.observations").inc(len(it_obs))
    legal_hist.observe(legal_n / max(1, n_props))
    for o in it_obs:
        if o.cost is not None and not math.isinf(o.cost):
            best_gauge.min(o.cost)
    if on_iteration is not None:
        on_iteration(it, it_obs)
    if verbose and evaluated:
        cfg, area, (cost, _, _) = evaluated[0]
        # PimTuner.fit reports its model losses; other strategies None
        fit_str = "" if not isinstance(fit_info, dict) else " " + " ".join(
            f"{k}_loss={v:.3g}" for k, v in fit_info.items())
        print(f"[dse:{getattr(strategy, 'name', 'nicepim')}] it={it} "
              f"mapped={len(evaluated)} cfg={cfg.as_tuple()} "
              f"area={area:.1f} "
              f"cost={cost if not math.isinf(cost) else 'inf'} "
              f"({time.time() - t0:.1f}s){fit_str}")
    return it_obs


def _dse_iteration(strategy, evaluator, it, propose_k, cons, verbose,
                   pareto, on_iteration, evaluate_all_legal, sname,
                   best_gauge, legal_hist, batch_area_mm2,
                   props=None, propose_next=None) -> list[Observation]:
    with trace.span("iteration", strategy=sname, it=it):
        t0 = time.time()
        props, it_obs, to_eval, legal_n = propose_screen(
            strategy, it, propose_k, cons, sname, evaluate_all_legal,
            batch_area_mm2, props=props)
        evaluated: list[tuple[HwConfig, float, tuple]] = []
        if evaluate_all_legal:
            if to_eval:
                # every legal proposal is mapped, batched across configs
                results = evaluator.evaluate_batch(
                    [cfg for cfg, _ in to_eval])
                evaluated = [(cfg, area, res) for (cfg, area), res
                             in zip(to_eval, results)]
        elif to_eval:
            cfg, area = to_eval[0]
            evaluated = [(cfg, area, evaluator(cfg))]
        ingest_results(strategy, it, it_obs, evaluated, pareto, sname,
                       best_gauge, legal_hist, legal_n, len(props),
                       on_iteration, verbose, t0,
                       propose_next=propose_next)
    return it_obs
