"""Comparison DSE strategies for Fig. 9: Random, SimAnneal, plain GP, GBT.

Each strategy implements ``observe(cfg, cost)`` + ``propose(k)`` so the DSE
driver (core/dse.py) can swap them for the NicePIM tuner.  ``GBTSurrogate``
is a from-scratch gradient-boosted-tree regressor standing in for XGBoost
(unavailable offline); ``GPSurrogate`` is an exact RBF GP on the raw
normalized parameters (no learned feature extractor — the ablation the paper
runs against deep kernel learning).

Candidate batches are drawn through the vectorized
:func:`repro.core.hardware.sample_config_values` (bitwise-identical to the
scalar ``tuner.sample_configs`` under a shared seed), and ``GPSurrogate``
scores them through the engine's shared masked-GP primitives
(:func:`repro.engine.tuner_train.score_candidates_raw`) so the Fig. 9
ablation and the deep-kernel tuner run one code path; ``backend="numpy"``
keeps the original float64 reference ranking for the parity tests.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from .hardware import (DEFAULT_CONSTRAINTS, HwConfig, PimConstraints,
                       configs_from_rows, normalize_params,
                       normalize_params_batch, sample_config_values,
                       sample_configs_batch, sample_space)

class _Base:
    def __init__(self, cons: PimConstraints = DEFAULT_CONSTRAINTS,
                 seed: int = 0, n_sample: int = 2048):
        self.cons = cons
        self.rng = np.random.default_rng(seed)
        self.n_sample = n_sample
        self._x: list[list[float]] = []
        self._y: list[float] = []

    def observe(self, cfg: HwConfig, area_mm2: float, cost: float | None):
        if cost is not None:
            self._x.append(normalize_params(cfg))
            self._y.append(math.log(max(cost, 1e-30)))

    def fit(self):
        pass


class RandomSearch(_Base):
    name = "random"

    def propose(self, k: int = 8) -> list[HwConfig]:
        return sample_configs_batch(k, self.rng, self.cons)


class SimulatedAnnealing(_Base):
    """Random-walk annealing over the discrete parameter grid."""

    name = "simanneal"

    def __init__(self, cons=DEFAULT_CONSTRAINTS, seed: int = 0,
                 n_sample: int = 2048, t0: float = 1.0, decay: float = 0.92):
        super().__init__(cons, seed, n_sample)
        self.t = t0
        self.decay = decay
        self.cur: HwConfig | None = None
        self.cur_cost = math.inf

    def observe(self, cfg: HwConfig, area_mm2: float, cost: float | None):
        super().observe(cfg, area_mm2, cost)
        if cost is None:
            return
        c = math.log(max(cost, 1e-30))
        if (self.cur is None or c < self.cur_cost or
                self.rng.random() < math.exp(-(c - self.cur_cost) /
                                             max(self.t, 1e-6))):
            self.cur = cfg
            self.cur_cost = c
        self.t *= self.decay

    def _neighbor(self, cfg: HwConfig) -> HwConfig:
        space = sample_space(self.cons)
        keys = list(space)
        for _ in range(64):
            k = keys[self.rng.integers(len(keys))]
            vals = space[k]
            cur = getattr(cfg, k)
            i = min(range(len(vals)), key=lambda j: abs(vals[j] - cur))
            j = int(np.clip(i + self.rng.integers(-2, 3), 0, len(vals) - 1))
            cand = cfg.replace(**{k: vals[j]})
            if cand.legal_shape():
                return cand
        return cfg

    def propose(self, k: int = 8) -> list[HwConfig]:
        if self.cur is None:
            return sample_configs_batch(k, self.rng, self.cons)
        return [self._neighbor(self.cur) for _ in range(k)]


class GPSurrogate(_Base):
    """Exact RBF GP on raw params (median-heuristic lengthscale).

    ``backend="engine"`` (default) scores candidates through the shared
    masked-Cholesky / LCB primitives in :mod:`repro.engine.tuner_train`
    (float64, pow2-padded — one jitted dispatch per candidate batch; the
    f32-only Pallas ``lcb_rows`` kernel is not used, on any backend);
    ``backend="numpy"`` is the original dense reference, kept for parity.
    """

    name = "gp"

    # the tuner's backend vocabulary maps onto the GP's engine/reference split
    _BACKEND_ALIASES = {"scan": "engine", "loop": "numpy"}

    def __init__(self, cons=DEFAULT_CONSTRAINTS, seed: int = 0,
                 n_sample: int = 2048, beta: float = 1.0,
                 backend: str = "engine"):
        super().__init__(cons, seed, n_sample)
        self.beta = beta
        self.backend = self._BACKEND_ALIASES.get(backend, backend)
        if self.backend not in ("engine", "numpy"):
            raise ValueError(f"GPSurrogate backend must be 'engine' or "
                             f"'numpy' (or the tuner aliases 'scan'/'loop'), "
                             f"got {backend!r}")

    def _rank(self, xq: np.ndarray) -> np.ndarray:
        """Float64 numpy reference (the engine path's parity target)."""
        x = np.array(self._x)
        y = np.array(self._y)
        mu, sd = y.mean(), y.std() + 1e-9
        yn = (y - mu) / sd
        d2 = ((x[:, None, :] - x[None, :, :]) ** 2).sum(-1)
        ls2 = np.median(d2[d2 > 0]) if (d2 > 0).any() else 1.0
        k = np.exp(-0.5 * d2 / ls2) + 1e-3 * np.eye(len(x))
        kinv_y = np.linalg.solve(k, yn)
        dq2 = ((xq[:, None, :] - x[None, :, :]) ** 2).sum(-1)
        kq = np.exp(-0.5 * dq2 / ls2)
        mean = kq @ kinv_y
        var = np.clip(1.0 - np.einsum("qi,ij,qj->q", kq,
                                      np.linalg.inv(k), kq), 1e-9, None)
        return mean - self.beta * np.sqrt(var)

    def _rank_engine(self, xq: np.ndarray) -> np.ndarray:
        from ..engine.tuner_train import pow2_bucket, score_candidates_raw
        from ..runtime import x64
        x = np.array(self._x, np.float64)
        y = np.array(self._y, np.float64)
        n = len(y)
        p = pow2_bucket(n)
        xp = np.zeros((p, x.shape[1]))
        yp = np.zeros((p,))
        mask = np.zeros((p,), bool)
        xp[:n], yp[:n], mask[:n] = x, y, True
        with x64():
            scores = score_candidates_raw(
                jnp.asarray(xp), jnp.asarray(yp), jnp.asarray(mask),
                jnp.asarray(np.asarray(xq, np.float64)),
                jnp.ones(len(xq), bool), self.beta)
        return np.asarray(scores)

    def propose(self, k: int = 8) -> list[HwConfig]:
        vals = sample_config_values(self.n_sample, self.rng, self.cons)
        if len(self._y) < 3:
            return [HwConfig.from_tuple(map(int, row), cons=self.cons)
                    for row in vals[:k]]
        xq = normalize_params_batch(vals, dtype=np.float64)
        scores = self._rank(xq) if self.backend == "numpy" \
            else self._rank_engine(xq)
        return configs_from_rows(vals, self.cons,
                                 np.argsort(scores, kind="stable"), k)


# -- tiny gradient-boosted trees (XGBoost stand-in) ---------------------------


@dataclass
class _Stump:
    feat: int
    thresh: float
    left: float
    right: float

    def predict(self, x: np.ndarray) -> np.ndarray:
        return np.where(x[:, self.feat] <= self.thresh, self.left, self.right)


def _fit_stump(x: np.ndarray, r: np.ndarray, rng) -> _Stump:
    n, d = x.shape
    best = None
    best_err = math.inf
    feats = rng.choice(d, size=min(d, 5), replace=False)
    for f in feats:
        vals = np.unique(x[:, f])
        if len(vals) < 2:
            continue
        for t in np.quantile(vals, [0.25, 0.5, 0.75]):
            m = x[:, f] <= t
            if m.sum() == 0 or (~m).sum() == 0:
                continue
            lv, rv = r[m].mean(), r[~m].mean()
            err = ((r - np.where(m, lv, rv)) ** 2).sum()
            if err < best_err:
                best_err = err
                best = _Stump(int(f), float(t), float(lv), float(rv))
    return best or _Stump(0, 0.5, float(r.mean()), float(r.mean()))


class GBTSurrogate(_Base):
    """Gradient-boosted stumps with squared loss (XGBoost stand-in)."""

    name = "gbt"

    def __init__(self, cons=DEFAULT_CONSTRAINTS, seed: int = 0,
                 n_sample: int = 2048, n_trees: int = 120, lr: float = 0.15):
        super().__init__(cons, seed, n_sample)
        self.n_trees = n_trees
        self.lr = lr
        self._trees: list[_Stump] = []
        self._bias = 0.0

    def fit(self):
        if len(self._y) < 4:
            return
        x = np.array(self._x)
        y = np.array(self._y)
        self._bias = float(y.mean())
        pred = np.full(len(y), self._bias)
        self._trees = []
        for _ in range(self.n_trees):
            stump = _fit_stump(x, y - pred, self.rng)
            pred = pred + self.lr * stump.predict(x)
            self._trees.append(stump)

    def _predict(self, xq: np.ndarray) -> np.ndarray:
        pred = np.full(len(xq), self._bias)
        for t in self._trees:
            pred = pred + self.lr * t.predict(xq)
        return pred

    def propose(self, k: int = 8) -> list[HwConfig]:
        vals = sample_config_values(self.n_sample, self.rng, self.cons)
        if not self._trees:
            return [HwConfig.from_tuple(map(int, row), cons=self.cons)
                    for row in vals[:k]]
        xq = normalize_params_batch(vals, dtype=np.float64)
        return configs_from_rows(
            vals, self.cons,
            np.argsort(self._predict(xq), kind="stable"), k)


def make_strategy(name: str, cons=DEFAULT_CONSTRAINTS, seed: int = 0,
                  n_sample: int = 2048, backend: str | None = None):
    """Factory covering every Fig. 9 curve (incl. the NicePIM tuner).

    ``backend`` threads into the strategies that have an engine/reference
    split: the NicePIM tuner (``"scan"``/``"loop"``) and the GP ablation
    (``"engine"``/``"numpy"``); the rest ignore it.
    """
    from .tuner import PimTuner
    name = name.lower()
    if name in ("nicepim", "dkl"):
        return PimTuner(cons=cons, seed=seed, n_sample=n_sample,
                        backend=backend or "scan")
    if name == "gp":
        return GPSurrogate(cons, seed, n_sample, backend=backend or "engine")
    cls = {"random": RandomSearch, "simanneal": SimulatedAnnealing,
           "gbt": GBTSurrogate, "xgboost": GBTSurrogate}[name]
    return cls(cons, seed, n_sample)
