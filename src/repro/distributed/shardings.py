"""Sharding rules: parameter / batch / cache PartitionSpecs per architecture.

The rules mirror NicePIM's LM dimension choices on the TPU mesh (DESIGN.md
§3): output-channel-style dims (attention head projections, FFN hidden,
MoE experts, vocab) shard over ``model``; the batch dim shards over
``pod`` x ``data``; with ``fsdp=True`` the contraction dim of each large
matrix additionally shards over ``data`` (ZeRO-3 style — GSPMD inserts the
per-layer all-gathers inside the scan body, which is the WR<full-replication
regime of the paper).

Every rule is divisibility-guarded: an axis that does not evenly divide the
tensor dim is dropped (replicated) rather than failing to lower.
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

def get_abstract_mesh():
    """The ambient abstract mesh, or None outside a ``set_mesh`` scope."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return None
    return mesh


def set_mesh(mesh: Mesh):
    """Context manager entering ``mesh`` as the ambient mesh."""
    return jax.sharding.set_mesh(mesh)


def make_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with explicit Auto axis types."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    out = 1
    for a in axes:
        out *= mesh.shape[a]
    return out


def _fit(mesh: Mesh, dim: int, axes):
    """Return ``axes`` if they evenly divide ``dim`` else None (replicate)."""
    n = _axis_size(mesh, axes)
    return axes if (n > 1 and dim % n == 0) else None


def data_axes(mesh: Mesh):
    ax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return ax if len(ax) > 1 else (ax[0] if ax else None)


def constrain(x, *dims):
    """``with_sharding_constraint`` against the ambient abstract mesh.

    ``dims`` entries are axis names, tuples of axis names, or None; entries
    whose axes are absent from the ambient mesh or do not divide the dim are
    dropped.  No-op outside a ``jax.sharding.set_mesh`` scope, so model code
    can call this unconditionally (CPU tests see the identity).
    """
    mesh = get_abstract_mesh()
    if mesh is None:
        return x
    names = set(mesh.axis_names)

    def fit(i, axes):
        if axes is None:
            return None
        if isinstance(axes, str):
            axes = (axes,)
        axes = tuple(a for a in axes if a in names)
        if not axes:
            return None
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        return (axes if len(axes) > 1 else axes[0]) \
            if x.shape[i] % n == 0 and n > 1 else None

    spec = P(*(fit(i, a) for i, a in enumerate(dims)))
    return jax.lax.with_sharding_constraint(x, spec)


BATCH_AXES = ("pod", "data")


def attn_constraints(q, k, v):
    """Tensor-parallel layout for attention activations.

    Heads shard over ``model`` when they divide it (Megatron-style); when
    they don't (e.g. qwen2's 14 heads on a 16-way axis), the query *sequence*
    dim shards over ``model`` instead (sequence parallelism) and K/V
    replicate — attention work stays fully partitioned either way, instead
    of GSPMD silently replicating it (16x redundant FLOPs) or sharding the
    contraction dim (full-scores all-reduce).
    """
    mesh = get_abstract_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return q, k, v
    msize = mesh.shape["model"]
    if msize <= 1:
        return q, k, v
    if q.shape[2] % msize == 0:
        q = constrain(q, BATCH_AXES, None, "model", None)
        k = constrain(k, BATCH_AXES, None, "model", None)
        v = constrain(v, BATCH_AXES, None, "model", None)
    elif q.shape[1] % msize == 0:
        q = constrain(q, BATCH_AXES, "model", None, None)
        k = constrain(k, BATCH_AXES, None, None, None)
        v = constrain(v, BATCH_AXES, None, None, None)
    return q, k, v


def param_specs(cfg, params: Any, mesh: Mesh, *, fsdp: bool = False,
                tp: bool = True):
    """PartitionSpec pytree matching ``params`` (from nn.init_params).

    ``tp=False`` drops the `model` axis everywhere (fully replicated
    parameters — the serving analogue of the paper's WR=full replication).
    """
    dp = data_axes(mesh) if fsdp else None

    def spec_for(path: str, x) -> P:
        shape = x.shape
        nd = x.ndim

        def d(i, axes):
            if not tp:
                if axes == "model":
                    return None
                if isinstance(axes, tuple) and "model" in axes:
                    axes = tuple(a for a in axes if a != "model") or None
            return _fit(mesh, shape[i], axes)

        if path.endswith("embed"):
            # vocab over model only: sharding the feature dim too turns the
            # token gather into an SPMD full-rematerialization
            return P(d(0, "model"), None)
        if path.endswith("head"):
            return P(d(0, dp), d(1, "model"))
        if "final_norm" in path:
            return P(None)
        # stacked per-layer params: axis 0 is the layer axis
        leaf = path.split("/")[-1]
        if nd == 3 and leaf in ("wq", "wk", "wv", "w1", "w3", "ck",
                                "wx", "wy", "wr", "wk", "wv", "wg", "wd1"):
            return P(None, d(1, dp), d(2, "model"))
        if nd == 3 and leaf in ("wo", "w2", "cv", "wd2"):
            return P(None, d(1, "model"), d(2, dp))
        if nd == 4 and leaf in ("we1", "we3", "we2"):   # MoE experts
            return P(None, d(1, "model"), d(2, dp), None)
        if nd == 3 and leaf == "router":
            return P(None, d(1, dp), None)
        if nd == 3 and leaf == "conv_w":
            return P(None, None, d(2, "model"))
        if nd == 3 and leaf == "u":                     # rwkv bonus (L,H,dh)
            return P(None, d(1, "model"), None)
        if nd == 2 and leaf in ("bq", "bk", "bv"):
            return P(None, d(1, "model"))
        if nd == 2 and leaf in ("wr_diag", "wi_diag", "br", "bi", "lambda"):
            return P(None, d(1, "model"))
        return P(*([None] * nd))  # norms, mus, scalars

    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    specs = {}

    def build(tree, prefix=""):
        if isinstance(tree, dict):
            return {k: build(v, f"{prefix}/{k}") for k, v in tree.items()}
        return spec_for(prefix, tree)

    return build(params)


def shardings_for(cfg, params, mesh: Mesh, *, fsdp: bool = False):
    specs = param_specs(cfg, params, mesh, fsdp=fsdp)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def batch_spec(mesh: Mesh, global_batch: int) -> P:
    dp = data_axes(mesh)
    return P(_fit(mesh, global_batch, dp), None)


def batch_specs(cfg, mesh: Mesh, batch: Any, *, is_embeds: bool = False):
    """Specs for a train/prefill batch dict (tokens/targets/embeds...)."""
    def one(x):
        dp = data_axes(mesh)
        b = _fit(mesh, x.shape[0], dp)
        if x.ndim == 3:   # precomputed frontend embeddings (B, S, D)
            return P(b, None, _fit(mesh, x.shape[-1], "model"))
        return P(*([b] + [None] * (x.ndim - 1)))
    return jax.tree.map(one, batch)


def cache_specs(cfg, mesh: Mesh, cache: Any):
    """Decode-cache specs: batch over data axes, heads/channels over model."""
    dp = data_axes(mesh)

    def one(path, x):
        leaf = path[-1].key if path else ""
        s = x.shape
        if leaf in ("k", "v"):          # (L, B, T, Hkv, dh)
            heads = _fit(mesh, s[3], "model")
            # GQA caches whose few KV heads don't divide the model axis
            # shard the time dim instead (32k-ctx caches are 10s of GB/chip
            # if replicated); softmax reductions over sharded T are handled
            # by GSPMD.
            time_ax = _fit(mesh, s[2], "model") if heads is None else None
            return P(None, _fit(mesh, s[1], dp), time_ax, heads, None)
        if leaf == "kpos":              # (L, B, T)
            return P(None, _fit(mesh, s[1], dp), None)
        if leaf == "S":                 # (L, B, H, dh, dh)
            return P(None, _fit(mesh, s[1], dp),
                     _fit(mesh, s[2], "model"), None, None)
        if leaf in ("shift_t", "shift_c", "h"):   # (L, B, D)
            return P(None, _fit(mesh, s[1], dp), _fit(mesh, s[2], "model"))
        if leaf == "conv":              # (L, B, W-1, D)
            return P(None, _fit(mesh, s[1], dp), None,
                     _fit(mesh, s[3], "model"))
        return P(*([None] * x.ndim))

    return jax.tree_util.tree_map_with_path(one, cache)


def opt_state_specs(param_spec_tree, opt_state):
    """Adam mu/nu shard exactly like their parameters; step is replicated."""
    from repro.training.optim import AdamState
    return AdamState(P(), param_spec_tree, param_spec_tree)
