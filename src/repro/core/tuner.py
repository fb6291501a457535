"""PIM-Tuner (Sec. V): filter MLP + deep-kernel-learning suggestion model.

Both models are pure JAX, trained with the from-scratch Adam in
``repro.training.optim``:

* **Filter model** — MLP with 256/64/16/1 ReLU layers (paper Sec. VIII-B)
  regressing the logic-die area from the normalized 7-d hardware parameter
  vector; candidates whose predicted area exceeds the constraint are
  discarded before ranking.
* **Suggestion model** — deep kernel learning [27]: an MLP feature extractor
  (256/64/16) feeding an RBF Gaussian process; MLP weights and GP
  hyperparameters (lengthscale, signal, noise) are optimized *jointly* by
  maximizing the exact GP log marginal likelihood.  Ranking uses a lower
  confidence bound on the predicted (standardized log-)cost.

Both models run on one of two backends:

* ``backend="scan"`` (default) — the engine layer
  (:mod:`repro.engine.tuner_train`): the whole Adam trajectory runs inside
  one jitted ``lax.scan`` over pow2-bucketed, validity-masked data (no
  per-step host round-trips, no recompile per growing dataset size), propose
  scoring is one fused jitted dispatch over the full candidate batch (area
  mask applied in-array), and candidates are drawn through the vectorized
  :func:`repro.core.hardware.sample_config_values`.
* ``backend="loop"`` — the original per-step host-dispatch reference path,
  kept as the parity baseline for ``tests/test_tuner_engine.py`` and the
  scalar side of ``benchmarks/tuner_throughput.py``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..engine.tuner_train import (dkl_features, fit_dkl, fit_filter,
                                  mlp_forward, mlp_init, pad_dataset,
                                  rbf_cross, score_candidates)
from ..runtime import native_kernels
from ..training.optim import Adam
from .hardware import HwConfig, PimConstraints, DEFAULT_CONSTRAINTS, \
    configs_from_rows, normalize_params, normalize_params_batch, \
    sample_config_values, sample_space

# shared model primitives live in the engine layer (one code path for the
# scan backend, these references, and the Fig. 9 GP ablation)
_init_mlp = mlp_init
_mlp_forward = mlp_forward
_features = dkl_features


def _check_backend(backend: str) -> str:
    if backend not in ("scan", "loop"):
        raise ValueError(f"tuner backend must be 'scan' or 'loop', "
                         f"got {backend!r}")
    return backend


# ---------------------------------------------------------------------------
# Filter model
# ---------------------------------------------------------------------------

FILTER_SIZES = [7, 256, 64, 16, 1]


@jax.jit
def _filter_loss(params, x, y):
    pred = _mlp_forward(params, x)[:, 0]
    return jnp.mean((pred - y) ** 2)


@jax.jit
def _filter_step(params, opt_state, x, y):
    loss, grads = jax.value_and_grad(_filter_loss)(params, x, y)
    params, opt_state = _FILTER_OPT.apply(grads, opt_state, params)
    return params, opt_state, loss


@jax.jit
def _filter_forward(params, x):
    return _mlp_forward(params, x)[:, 0]


_FILTER_OPT = Adam(lr=3e-3)


class FilterModel:
    """Predicts log(area/budget) from hw params (area spans ~4 decades)."""

    def __init__(self, cons: PimConstraints = DEFAULT_CONSTRAINTS,
                 seed: int = 0, backend: str = "scan"):
        self.cons = cons
        self.backend = _check_backend(backend)
        self.params = _init_mlp(jax.random.PRNGKey(seed), FILTER_SIZES)
        self.opt_state = _FILTER_OPT.init(self.params)
        self._x: list[list[float]] = []
        self._y: list[float] = []

    def add(self, cfg: HwConfig, area_mm2: float) -> None:
        self._x.append(normalize_params(cfg))
        self._y.append(math.log(max(area_mm2, 1e-6) /
                                self.cons.area_budget_mm2))

    def fit(self, steps: int = 200) -> float:
        if len(self._y) < 8:
            return float("nan")
        if self.backend == "loop":
            x = np.array(self._x, np.float32)
            y = np.array(self._y, np.float32)
            xj, yj = jnp.asarray(x), jnp.asarray(y)
            loss = jnp.inf
            for _ in range(steps):
                self.params, self.opt_state, loss = _filter_step(
                    self.params, self.opt_state, xj, yj)
            return float(loss)
        return float(self.fit_arrays(steps)[-1])

    def fit_arrays(self, steps: int = 200):
        """Scan-backend fit WITHOUT the final-loss host sync.

        Returns the device-resident loss trajectory (``None`` when there
        are too few observations) — the device-resident pipeline's hook:
        the dispatch is enqueued asynchronously and the host never blocks
        on it unless someone actually reads a loss.  Model state updates
        are identical to :meth:`fit`.
        """
        if len(self._y) < 8:
            return None
        x = np.array(self._x, np.float32)
        y = np.array(self._y, np.float32)
        # explicit put: the training-set staging is the ONE host->device
        # hop of a fit, so the pipeline's transfer guard stays clean
        xp, yp, mask = map(jax.device_put, pad_dataset(x, y))
        self.params, self.opt_state, losses = fit_filter(
            self.params, self.opt_state, xp, yp, mask,
            opt=_FILTER_OPT, steps=steps)
        return losses

    def predict_area_x(self, x: np.ndarray) -> np.ndarray:
        """Predicted areas (mm^2) for an ``[n, 7]`` normalized-param matrix."""
        pred = _filter_forward(self.params, jnp.asarray(x, jnp.float32))
        return np.exp(np.asarray(pred)) * self.cons.area_budget_mm2

    def predict_area(self, cfgs: list[HwConfig]) -> np.ndarray:
        return self.predict_area_x(
            np.array([normalize_params(c) for c in cfgs], np.float32))

    def trained(self) -> bool:
        return len(self._y) >= 8


# ---------------------------------------------------------------------------
# Deep-kernel-learning suggestion model
# ---------------------------------------------------------------------------

DKL_SIZES = [7, 256, 64, 16]


def _dkl_init(seed: int) -> dict:
    return {
        "mlp": _init_mlp(jax.random.PRNGKey(seed), DKL_SIZES),
        "log_ls": jnp.zeros(()),       # RBF lengthscale
        "log_sf": jnp.zeros(()),       # signal stddev
        # strong f32 (a weak-typed scalar here would flip type after the
        # first fit and force one spurious recompile per shape bucket)
        "log_sn": jnp.asarray(-2.0, jnp.float32),
    }


def _kernel(params, za, zb):
    # shares the engine's gram-trick RBF so both backends run identical ops
    ls = jnp.exp(params["log_ls"])
    sf2 = jnp.exp(2 * params["log_sf"])
    return rbf_cross(za, zb, ls ** 2 + 1e-8, sf2)


@jax.jit
def _nlml(params, x, y):
    """Negative log marginal likelihood of the exact GP."""
    z = _features(params, x)
    n = x.shape[0]
    k = _kernel(params, z, z) + (jnp.exp(2 * params["log_sn"]) + 1e-6) \
        * jnp.eye(n)
    chol = jnp.linalg.cholesky(k)
    alpha = jax.scipy.linalg.cho_solve((chol, True), y)
    return (0.5 * y @ alpha + jnp.sum(jnp.log(jnp.diag(chol)))
            + 0.5 * n * jnp.log(2 * jnp.pi)) / n


_DKL_OPT = Adam(lr=3e-3, clip_norm=10.0)


@jax.jit
def _dkl_step(params, opt_state, x, y):
    loss, grads = jax.value_and_grad(_nlml)(params, x, y)
    params, opt_state = _DKL_OPT.apply(grads, opt_state, params)
    return params, opt_state, loss


@jax.jit
def _dkl_predict(params, x_train, y_train, x_query):
    zt = _features(params, x_train)
    zq = _features(params, x_query)
    n = x_train.shape[0]
    k = _kernel(params, zt, zt) + (jnp.exp(2 * params["log_sn"]) + 1e-6) \
        * jnp.eye(n)
    chol = jnp.linalg.cholesky(k)
    alpha = jax.scipy.linalg.cho_solve((chol, True), y_train)
    kq = _kernel(params, zq, zt)
    mean = kq @ alpha
    v = jax.scipy.linalg.solve_triangular(chol, kq.T, lower=True)
    var = jnp.exp(2 * params["log_sf"]) - jnp.sum(v * v, axis=0)
    return mean, jnp.clip(var, 1e-9)


class DklSuggestionModel:
    """Ranks hardware configs by LCB of predicted standardized log-cost."""

    name = "dkl"

    def __init__(self, seed: int = 0, beta: float = 1.0,
                 backend: str = "scan"):
        self.params = _dkl_init(seed)
        self.opt_state = _DKL_OPT.init(self.params)
        self.beta = beta
        self.backend = _check_backend(backend)
        self._x: list[list[float]] = []
        self._y: list[float] = []
        self._mu = 0.0
        self._sigma = 1.0
        # observations added after the last fit() invalidate the GP state
        # AND the (_mu, _sigma) standardization; rank() refits when dirty
        # instead of scoring against stale statistics
        self._dirty = True
        self._train: tuple | None = None   # padded (x, y, mask) of last fit

    def add(self, cfg: HwConfig, cost: float) -> None:
        self._x.append(normalize_params(cfg))
        self._y.append(math.log(max(cost, 1e-30)))
        self._dirty = True

    def fit(self, steps: int = 300) -> float:
        if len(self._y) < 3:
            return float("nan")
        if self.backend == "loop":
            y = np.array(self._y, np.float64)
            self._mu = float(y.mean())
            self._sigma = float(y.std() + 1e-9)
            x = np.array(self._x, np.float32)
            yn = ((y - self._mu) / self._sigma).astype(np.float32)
            xj, yj = jnp.asarray(x), jnp.asarray(yn)
            loss = jnp.inf
            for _ in range(steps):
                self.params, self.opt_state, loss = _dkl_step(
                    self.params, self.opt_state, xj, yj)
            self._dirty = False
            return float(loss)
        return float(self.fit_arrays(steps)[-1])

    def fit_arrays(self, steps: int = 300):
        """Scan-backend fit WITHOUT the final-loss host sync (see
        :meth:`FilterModel.fit_arrays`); returns ``None`` below 3 points."""
        if len(self._y) < 3:
            return None
        y = np.array(self._y, np.float64)
        self._mu = float(y.mean())
        self._sigma = float(y.std() + 1e-9)
        x = np.array(self._x, np.float32)
        yn = ((y - self._mu) / self._sigma).astype(np.float32)
        # device-resident training set: one explicit put per fit, and the
        # cached ``_train`` feeds propose scoring without another transfer
        xp, yp, mask = map(jax.device_put, pad_dataset(x, yn))
        self.params, self.opt_state, losses = fit_dkl(
            self.params, self.opt_state, xp, yp, mask,
            opt=_DKL_OPT, steps=steps)
        self._train = (xp, yp, mask)
        self._dirty = False
        return losses

    def rank_x(self, xq: np.ndarray,
               area_ok: np.ndarray | None = None) -> np.ndarray:
        """Scores for an ``[n, 7]`` normalized-param matrix (lower = better).

        ``area_ok`` is the filter model's in-array mask: candidates with
        ``area_ok=False`` score ``+inf`` so they sort last.  Stale models
        (observations added since the last ``fit``) are refit first.
        """
        if len(self._y) < 3:
            scores = np.zeros(len(xq))
            return scores if area_ok is None \
                else np.where(area_ok, scores, np.inf)
        if self._dirty:
            self.fit()
        if self.backend == "loop" or self._train is None:
            xt = jnp.asarray(np.array(self._x, np.float32))
            yt = jnp.asarray(((np.array(self._y) - self._mu)
                              / self._sigma).astype(np.float32))
            mean, var = _dkl_predict(self.params, xt, yt,
                                     jnp.asarray(xq, jnp.float32))
            scores = np.asarray(mean - self.beta * jnp.sqrt(var))
            return scores if area_ok is None \
                else np.where(area_ok, scores, np.inf)
        xp, yp, mask = self._train
        ok = np.ones(len(xq), bool) if area_ok is None else area_ok
        return np.asarray(score_candidates(
            self.params, xp, yp, mask, jnp.asarray(xq, jnp.float32),
            ok, self.beta, use_pallas=native_kernels()))

    def rank(self, cfgs: list[HwConfig]) -> np.ndarray:
        """Scores (lower = better); LCB on the predicted cost."""
        if len(self._y) < 3:
            return np.zeros(len(cfgs))
        return self.rank_x(np.array([normalize_params(c) for c in cfgs],
                                    np.float32))


# ---------------------------------------------------------------------------
# Sampling + the tuner driver
# ---------------------------------------------------------------------------


def sample_configs(n: int, rng: np.random.Generator,
                   cons: PimConstraints = DEFAULT_CONSTRAINTS,
                   max_draws: int | None = None) -> list[HwConfig]:
    """Uniform raw samples from the Table-II design space (shape-legal only).

    The scalar reference loop: one candidate per iteration, rejected through
    ``HwConfig.legal_shape``.  It consumes the generator stream exactly like
    the vectorized :func:`repro.core.hardware.sample_config_values`, so a
    shared seed yields identical samples (pinned by the parity tests).
    ``max_draws`` caps total attempts — a degenerate constraint set raises
    instead of spinning forever.
    """
    if max_draws is None:
        max_draws = 64 * n + 1024
    space = sample_space(cons)
    keys = list(space)
    outs = []
    draws = 0
    while len(outs) < n:
        if draws >= max_draws:
            raise RuntimeError(
                f"sample_configs: drew {draws} candidates but only "
                f"{len(outs)}/{n} passed legal_shape (draw cap {max_draws}); "
                f"the constraint set likely leaves no legal configurations")
        vals = {k: space[k][rng.integers(len(space[k]))] for k in keys}
        draws += 1
        cfg = HwConfig(cons=cons, **vals)
        if cfg.legal_shape():
            outs.append(cfg)
    return outs


@dataclass
class PimTuner:
    """One NicePIM tuner iteration: sample -> filter -> rank (Fig. 8)."""

    name = "nicepim"

    cons: PimConstraints = DEFAULT_CONSTRAINTS
    seed: int = 0
    n_sample: int = 2048
    beta: float = 1.0
    backend: str = "scan"
    filter_model: FilterModel = None
    suggestion: DklSuggestionModel = None

    def __post_init__(self):
        _check_backend(self.backend)
        self.rng = np.random.default_rng(self.seed)
        if self.filter_model is None:
            self.filter_model = FilterModel(self.cons, self.seed,
                                            backend=self.backend)
        if self.suggestion is None:
            self.suggestion = DklSuggestionModel(self.seed, self.beta,
                                                 backend=self.backend)

    def propose(self, k: int = 8) -> list[HwConfig]:
        if self.backend == "loop":
            return self._propose_loop(k)
        # the whole candidate batch as an [n, 7] value matrix: vectorized
        # draw, vectorized normalize, in-array area mask, one fused scoring
        # dispatch — HwConfig objects only materialize for the k winners
        vals = sample_config_values(self.n_sample, self.rng, self.cons)
        xq = normalize_params_batch(vals)
        area_ok = None
        if self.filter_model.trained():
            areas = self.filter_model.predict_area_x(xq)
            mask = areas <= self.cons.area_budget_mm2
            if mask.any():     # an all-reject filter would starve the search
                area_ok = mask
        scores = self.suggestion.rank_x(xq, area_ok=area_ok)
        # masked candidates score +inf and sort last; the valid mask stops
        # the dedup walk before it could surface one
        return configs_from_rows(vals, self.cons,
                                 np.argsort(scores, kind="stable"), k,
                                 valid=area_ok)

    def _propose_loop(self, k: int) -> list[HwConfig]:
        """The original list-based propose (scalar reference path)."""
        cands = sample_configs(self.n_sample, self.rng, self.cons)
        if self.filter_model.trained():
            areas = self.filter_model.predict_area(cands)
            keep = [c for c, a in zip(cands, areas)
                    if a <= self.cons.area_budget_mm2]
            if keep:
                cands = keep
        scores = self.suggestion.rank(cands)
        order = np.argsort(scores, kind="stable")
        seen, out = set(), []
        for i in order:
            t = cands[i].as_tuple()
            if t not in seen:
                seen.add(t)
                out.append(cands[i])
            if len(out) >= k:
                break
        return out

    def observe(self, cfg: HwConfig, area_mm2: float,
                cost: float | None) -> None:
        self.filter_model.add(cfg, area_mm2)
        if cost is not None:
            self.suggestion.add(cfg, cost)

    def fit(self) -> dict:
        return {"filter": self.filter_model.fit(),
                "dkl": self.suggestion.fit()}
