"""Jitted tuner engine: scan-based DKL/filter training and fused propose.

The PIM-Tuner's scalar path (Sec. V / Fig. 8) runs 200-300 host-side Adam
dispatches per DSE iteration and retraces both training steps on every
*growing* dataset shape — one fresh XLA program per iteration.  This module
moves the whole tuner/surrogate stack onto the engine layer:

* :func:`fit_filter` / :func:`fit_dkl` run the entire Adam trajectory inside
  ONE jitted ``lax.scan`` — no per-step host round-trips — with the training
  set padded into power-of-two buckets and a validity mask threaded through
  the masked MSE and the masked GP negative log marginal likelihood, so XLA
  compiles O(log n) distinct programs across a whole campaign instead of one
  per dataset size;
* :func:`score_candidates` (deep-kernel model) and
  :func:`score_candidates_raw` (the Fig. 9 raw-parameter GP ablation) score a
  full candidate batch in a single dispatch: MLP features, RBF cross-kernel,
  GP posterior mean/variance, and the LCB, with the filter-model area mask
  applied in-array (masked-out candidates score ``+inf``).  The
  pairwise-distance + LCB reduction can run in the f32 Pallas kernel
  :func:`repro.kernels.dse_eval.lcb_rows` (``use_pallas=True``; the models
  pass :func:`repro.runtime.native_kernels`, so a TPU runs the kernel and
  other backends the jnp path).

Masking contract (the jitter-on-the-padded-diagonal trick): padded
rows/columns of the training kernel are zeroed and their diagonal pinned to
1, so the Cholesky factor is block-diagonal and its valid block is exactly
the unpadded factor; padded targets are zeroed so ``alpha = K^-1 y`` has
zero padded entries, and the padded block of ``K^-1`` is the identity —
which the masked cross-kernel never touches.  Masked losses and predictions
therefore equal the unpadded exact values up to float reassociation
(``tests/test_tuner_engine.py`` pins both the scan-vs-loop trajectories and
the padded-vs-unpadded predictions).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import dse_eval
from ..obs import metrics, trace
from ..training.optim import Adam

MIN_BUCKET = 8


# ---------------------------------------------------------------------------
# pow2 bucketing
# ---------------------------------------------------------------------------


def pow2_bucket(n: int, minimum: int = MIN_BUCKET) -> int:
    """Smallest power-of-two >= n (floored at ``minimum``)."""
    return max(minimum, 1 << max(0, (int(n) - 1).bit_length()))


def pad_dataset(x, y) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pad ``(x [n,d], y [n])`` to the pow2 bucket; returns (x, y, mask).

    Padded rows are zero (harmless through the masked losses) and masked
    invalid; the bucket keeps the XLA program count logarithmic in the
    number of accumulated observations.
    """
    x = np.asarray(x, np.float32)
    y = np.asarray(y, np.float32)
    n = y.shape[0]
    p = pow2_bucket(n)
    xp = np.zeros((p, x.shape[1]), np.float32)
    yp = np.zeros((p,), np.float32)
    mask = np.zeros((p,), bool)
    xp[:n] = x
    yp[:n] = y
    mask[:n] = True
    return xp, yp, mask


# ---------------------------------------------------------------------------
# Model primitives (shared with core/tuner.py's scalar-loop reference)
# ---------------------------------------------------------------------------


def mlp_init(key, sizes: list[int]) -> list[dict]:
    layers = []
    for a, b in zip(sizes[:-1], sizes[1:]):
        key, k1 = jax.random.split(key)
        w = jax.random.normal(k1, (a, b), jnp.float32) * math.sqrt(2.0 / a)
        layers.append({"w": w, "b": jnp.zeros((b,), jnp.float32)})
    return layers


def mlp_forward(layers: list[dict], x: jnp.ndarray) -> jnp.ndarray:
    h = x
    for i, l in enumerate(layers):
        h = h @ l["w"] + l["b"]
        if i < len(layers) - 1:
            h = jax.nn.relu(h)
    return h


def dkl_features(params: dict, x: jnp.ndarray,
                 mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """Normalized MLP features (the deep kernel's learned embedding).

    ``mask`` marks valid rows of a padded batch.  Padded rows produce the
    zero vector, where the norm's gradient is NaN; the double-where trick
    routes them through a safe constant instead (their value never matters:
    every downstream kernel entry involving a padded row is masked out, and
    the constant blocks the NaN from poisoning the whole gradient).
    """
    z = mlp_forward(params["mlp"], x)
    if mask is not None:
        z = jnp.where(mask[:, None], z, 1.0)
    zn = z / (jnp.linalg.norm(z, axis=-1, keepdims=True) + 1e-6)
    if mask is not None:
        zn = jnp.where(mask[:, None], zn, 0.0)
    return zn


def kernel_scalars(params: dict):
    """(effective lengthscale^2, signal var, noise var) of the DKL kernel."""
    ls2 = jnp.exp(params["log_ls"]) ** 2 + 1e-8
    sf2 = jnp.exp(2 * params["log_sf"])
    sn2 = jnp.exp(2 * params["log_sn"]) + 1e-6
    return ls2, sf2, sn2


def pairwise_sq_dists(za, zb):
    """``|za[i] - zb[j]|^2`` as [A, B] via the gram trick.

    One matmul instead of materializing the [A, B, D] broadcast difference —
    the hot op of both the per-step NLML kernel and the 2048-candidate
    propose cross-kernel.  Clamped at 0 (the expansion can go epsilon-
    negative in float32).
    """
    sq_a = jnp.sum(za * za, axis=-1)
    sq_b = jnp.sum(zb * zb, axis=-1)
    d2 = sq_a[:, None] + sq_b[None, :] - 2.0 * (za @ zb.T)
    return jnp.maximum(d2, 0.0)


def rbf_cross(za, zb, ls2, sf2):
    """RBF cross-kernel ``sf2 * exp(-|za - zb|^2 / (2 ls2))`` as [A, B]."""
    return sf2 * jnp.exp(-0.5 * pairwise_sq_dists(za, zb) / ls2)


def masked_kernel(z, mask, ls2, sf2, sn2):
    """Masked training kernel: valid block exact, padded block = identity."""
    k = rbf_cross(z, z, ls2, sf2)
    m2 = mask[:, None] & mask[None, :]
    k = jnp.where(m2, k, 0.0)
    return k + jnp.diag(jnp.where(mask, sn2, jnp.ones_like(sn2)))


# ---------------------------------------------------------------------------
# Masked losses
# ---------------------------------------------------------------------------


def masked_mse(params, x, y, mask):
    """Filter-model loss; equals ``mean((pred - y)^2)`` over the valid rows."""
    pred = mlp_forward(params, x)[:, 0]
    se = jnp.where(mask, (pred - y) ** 2, 0.0)
    return jnp.sum(se) / jnp.sum(mask.astype(se.dtype))


def masked_nlml(params, x, y, mask):
    """Masked GP NLML; equals the exact unpadded NLML of the valid subset."""
    z = dkl_features(params, x, mask)
    ls2, sf2, sn2 = kernel_scalars(params)
    k = masked_kernel(z, mask, ls2, sf2, sn2)
    chol = jnp.linalg.cholesky(k)
    ym = jnp.where(mask, y, 0.0)
    alpha = jax.scipy.linalg.cho_solve((chol, True), ym)
    nv = jnp.sum(mask.astype(ym.dtype))
    logdet = jnp.sum(jnp.where(mask, jnp.log(jnp.diag(chol)), 0.0))
    return (0.5 * ym @ alpha + logdet
            + 0.5 * nv * jnp.log(2 * jnp.pi)) / nv


# ---------------------------------------------------------------------------
# Scan-based training (one dispatch per fit, not one per Adam step)
# ---------------------------------------------------------------------------


def _scan_fit(loss_fn, opt: Adam, params, opt_state, args, steps: int):
    def step(carry, _):
        p, s = carry
        loss, grads = jax.value_and_grad(loss_fn)(p, *args)
        p, s = opt.apply(grads, s, p)
        return (p, s), loss
    # the per-step graph is hundreds of tiny CPU ops; a modest unroll
    # amortizes the loop bookkeeping without exploding compile time
    (params, opt_state), losses = jax.lax.scan(
        step, (params, opt_state), None, length=steps,
        unroll=min(4, steps))
    return params, opt_state, losses


# params/opt_state are donated: every trajectory returns a same-shaped
# (params, opt_state), so XLA updates the optimizer state in place.  Callers
# hand ownership over — the model classes reassign from the return value;
# anything re-running a fit from the SAME initial state must pass copies
# (``tests/test_pipeline.py`` pins that donated fits still match the loop
# references and that the inputs really are consumed).
@partial(jax.jit, static_argnames=("opt", "steps"), donate_argnums=(0, 1))
def _fit_filter_jit(params, opt_state, x, y, mask, *, opt: Adam, steps: int):
    return _scan_fit(masked_mse, opt, params, opt_state, (x, y, mask), steps)


@partial(jax.jit, static_argnames=("opt", "steps"), donate_argnums=(0, 1))
def _fit_dkl_jit(params, opt_state, x, y, mask, *, opt: Adam, steps: int):
    return _scan_fit(masked_nlml, opt, params, opt_state, (x, y, mask), steps)


def _record_bucket(kind: str, y, mask) -> None:
    """Pow2-bucket occupancy + padding-waste metrics for one fit dispatch.

    ``mask`` arrives concrete (the host built it in ``pad_dataset``), so
    summing it never blocks on an in-flight computation.
    """
    bucket = int(y.shape[0])
    valid = int(np.asarray(mask).sum())
    metrics.METRICS.gauge(f"tuner.bucket.{kind}").set(bucket)
    metrics.METRICS.histogram(f"tuner.bucket_fill.{kind}").observe(
        valid / bucket if bucket else 0.0)
    metrics.METRICS.counter(f"tuner.padded_rows.{kind}").inc(bucket - valid)


def fit_filter(params, opt_state, x, y, mask, *, opt: Adam, steps: int):
    """Whole filter-MLP Adam trajectory in one jitted scan.

    Returns ``(params, opt_state, losses [steps])``; matches ``steps``
    sequential ``core.tuner._filter_step`` calls on the unpadded data.
    """
    _record_bucket("filter", y, mask)
    with trace.span("fit_filter", cat="engine", bucket=int(y.shape[0]),
                    steps=int(steps)):
        return _fit_filter_jit(params, opt_state, x, y, mask,
                               opt=opt, steps=steps)


def fit_dkl(params, opt_state, x, y, mask, *, opt: Adam, steps: int):
    """Whole DKL (MLP + GP hyperparameter) trajectory in one jitted scan."""
    _record_bucket("dkl", y, mask)
    with trace.span("fit_dkl", cat="engine", bucket=int(y.shape[0]),
                    steps=int(steps)):
        return _fit_dkl_jit(params, opt_state, x, y, mask,
                            opt=opt, steps=steps)


# ---------------------------------------------------------------------------
# Fused propose scoring
# ---------------------------------------------------------------------------


def _posterior_state(z, y, mask, ls2, sf2, sn2):
    """(alpha, kinv) of the masked training kernel for posterior queries."""
    k = masked_kernel(z, mask, ls2, sf2, sn2)
    chol = jnp.linalg.cholesky(k)
    ym = jnp.where(mask, y, 0.0)
    alpha = jax.scipy.linalg.cho_solve((chol, True), ym)
    kinv = jax.scipy.linalg.cho_solve(
        (chol, True), jnp.eye(k.shape[0], dtype=k.dtype))
    return alpha, kinv


def _lcb(zq, zt, alpha, kinv, mask, ls2, sf2, beta, use_pallas: bool):
    if use_pallas:
        return dse_eval.lcb_rows(zq, zt, alpha, kinv, mask, ls2, sf2, beta)
    kq = rbf_cross(zq, zt, ls2, sf2)
    kq = jnp.where(mask[None, :], kq, 0.0)
    mean = kq @ alpha
    var = sf2 - jnp.sum((kq @ kinv) * kq, axis=-1)
    return mean - beta * jnp.sqrt(jnp.clip(var, 1e-9))


@jax.jit
def dkl_predict(params, xt, yt, mask, xq):
    """Masked GP posterior (mean, var) — the padded twin of ``_dkl_predict``."""
    ls2, sf2, sn2 = kernel_scalars(params)
    zt = dkl_features(params, xt, mask)
    zq = dkl_features(params, xq)
    alpha, kinv = _posterior_state(zt, yt, mask, ls2, sf2, sn2)
    kq = jnp.where(mask[None, :], rbf_cross(zq, zt, ls2, sf2), 0.0)
    mean = kq @ alpha
    var = sf2 - jnp.sum((kq @ kinv) * kq, axis=-1)
    return mean, jnp.clip(var, 1e-9)


@partial(jax.jit, static_argnames=("use_pallas",))
def _score_candidates_jit(params, xt, yt, mask, xq, area_ok, beta, *,
                          use_pallas: bool = False):
    ls2, sf2, sn2 = kernel_scalars(params)
    zt = dkl_features(params, xt, mask)
    zq = dkl_features(params, xq)
    alpha, kinv = _posterior_state(zt, yt, mask, ls2, sf2, sn2)
    lcb = _lcb(zq, zt, alpha, kinv, mask, ls2, sf2, beta, use_pallas)
    return jnp.where(area_ok, lcb, jnp.inf)


def score_candidates(params, xt, yt, mask, xq, area_ok, beta, *,
                     use_pallas: bool = False):
    """Fused DKL propose: one dispatch over the whole candidate batch.

    Computes the deep-kernel features of both the (padded, masked) training
    set and the query batch, the RBF cross-kernel, the GP posterior
    mean/variance, and the LCB ``mean - beta * sqrt(var)``; candidates with
    ``area_ok=False`` (the filter model's in-array area mask) score ``+inf``
    so they sort last without any Python-side list filtering.
    """
    with trace.span("score_candidates", cat="engine",
                    bucket=int(yt.shape[0]), candidates=int(xq.shape[0])):
        return _score_candidates_jit(params, xt, yt, mask, xq, area_ok,
                                     beta, use_pallas=use_pallas)


@partial(jax.jit, static_argnames=("use_pallas",))
def _score_candidates_raw_jit(xt, yt, mask, xq, area_ok, beta, *,
                              noise_var: float = 1e-3,
                              use_pallas: bool = False):
    d2 = jnp.sum((xt[:, None, :] - xt[None, :, :]) ** 2, -1)
    m2 = (mask[:, None] & mask[None, :]) & (d2 > 0)
    ls2 = jnp.nanmedian(jnp.where(m2, d2, jnp.nan))
    ls2 = jnp.where(jnp.isnan(ls2), jnp.ones_like(ls2), ls2)
    nv = jnp.sum(mask.astype(yt.dtype))
    mu = jnp.sum(jnp.where(mask, yt, 0.0)) / nv
    var_y = jnp.sum(jnp.where(mask, (yt - mu) ** 2, 0.0)) / nv
    sd = jnp.sqrt(var_y) + 1e-9
    yn = jnp.where(mask, (yt - mu) / sd, 0.0)
    one = jnp.ones((), xt.dtype)
    alpha, kinv = _posterior_state(xt, yn, mask, ls2, one,
                                   jnp.asarray(noise_var, xt.dtype))
    lcb = _lcb(xq, xt, alpha, kinv, mask, ls2, one, beta, use_pallas)
    return jnp.where(area_ok, lcb, jnp.inf)


def score_candidates_raw(xt, yt, mask, xq, area_ok, beta, *,
                         noise_var: float = 1e-3,
                         use_pallas: bool = False):
    """Raw-parameter GP scoring (Fig. 9 ``gp`` ablation), same primitives.

    Median-heuristic lengthscale on the raw normalized parameters, unit
    signal variance, ``noise_var`` jitter, y standardized over the valid
    rows — the exact model of ``GPSurrogate``'s numpy reference, expressed
    on the shared masked-Cholesky / LCB primitives.
    """
    with trace.span("score_candidates", cat="engine",
                    bucket=int(yt.shape[0]), candidates=int(xq.shape[0])):
        return _score_candidates_raw_jit(xt, yt, mask, xq, area_ok, beta,
                                         noise_var=noise_var,
                                         use_pallas=use_pallas)


# ---------------------------------------------------------------------------
# XLA program-count introspection (the O(log n) recompile contract)
# ---------------------------------------------------------------------------

_JITTED = {
    "fit_filter": _fit_filter_jit,
    "fit_dkl": _fit_dkl_jit,
    "score_candidates": _score_candidates_jit,
    "score_candidates_raw": _score_candidates_raw_jit,
    "dkl_predict": dkl_predict,
}


def compiled_program_count() -> dict[str, int]:
    """Per-entry-point XLA cache sizes (process-global; diff around a run).

    ``benchmarks/tuner_throughput.py`` asserts the growth across a DSE run
    stays logarithmic in the number of accumulated observations — the pow2
    bucketing contract.
    """
    out = {}
    for name, fn in _JITTED.items():
        try:
            out[name] = int(fn._cache_size())
        except Exception:       # cache introspection is best-effort per jax
            out[name] = -1
    return out
