"""Jit'd public wrappers for the Pallas kernels.

``interpret`` follows :func:`repro.runtime.resolve_interpret`: kernels run
natively on a TPU backend and in interpret mode elsewhere.  The
model code (nn/attention.py, nn/rwkv6.py, nn/rglru.py) calls these when
``cfg.attention_impl == "pallas"``.
"""

from __future__ import annotations

from ..runtime import resolve_interpret
from . import flash_attention as _fa
from . import rglru_scan as _rg
from . import rwkv6_wkv as _wkv


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset: int = 0, block_q: int = 128,
                    block_k: int = 128, interpret: bool | None = None):
    return _fa.flash_attention(
        q, k, v, causal=causal, window=window, q_offset=q_offset,
        block_q=block_q, block_k=block_k,
        interpret=resolve_interpret(interpret))


def rglru(a, x, *, block_s: int = 256, block_d: int = 512,
          interpret: bool | None = None):
    return _rg.rglru_scan(
        a, x, block_s=block_s, block_d=block_d,
        interpret=resolve_interpret(interpret))


def rwkv6(r, k, v, w, u, *, block_s: int = 128,
          interpret: bool | None = None):
    return _wkv.rwkv6_wkv(
        r, k, v, w, u, block_s=block_s,
        interpret=resolve_interpret(interpret))
