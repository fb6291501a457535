"""Overlapped wave executor: async paired-cost dispatch + late resolve.

The warm DSE iteration is dominated by ``map_many`` costing: every mapper
phase calls ``batch_part_cost_paired``, which pulls its result to host at
the dispatch site (``np.asarray`` right after ``_batch_cost``), so the
backtracking walk, ``_sharing_problem_list`` extraction, and
``schedule_many`` bucket dispatch all serialize behind device work that
XLA would happily run on background threads.  This module splits the
paired sweep into the two halves JAX's async dispatch already supports:

* :func:`dispatch_paired_latency` — the *dispatch* half.  It mirrors
  ``batch_part_cost_paired``'s bucketing exactly (same T-buckets, same
  ``spec_chunk`` blocks, same pow2 pair padding, the same ``_batch_cost``
  programs on the same inputs), but returns a :class:`PendingPairedCost`
  holding the ``[1, n_pad]`` device cycle rows instead of blocking.
* :class:`PendingPairedCost` — the *resolve* half.  ``latency_row()``
  blocks once, stitches the per-block rows back into pair order, divides
  cycles by the clock on the host (NumPy, like ``_finalize_result``: a
  TPU's emulated f64 division is not correctly rounded), and caches the
  host array — bitwise identical to the serial path's latencies.

:class:`OverlapExecutor` interleaves the two across waves: ``drive``
runs a phase generator (``PimMapper.map_many_phases``) that yields right
after each dispatch, and at every yield the executor advances the oldest
*deferred* generator (wave k−1's scheduling/accounting) by one step —
host work runs while wave k's costs are in flight.  Deferred generators
retire strictly FIFO and each is exhausted before its successor starts,
so cost accumulation order — and therefore every float result — matches
the serial schedule bit for bit.

``serial_dispatch()`` restores the status-quo timing (sync at the
dispatch site) for baseline benchmarking and A/B tests; the flag is
thread-local so per-tenant overlap composes with
``ShardedCampaign.eval_workers`` threads.
"""

from __future__ import annotations

import threading
from collections import deque
from contextlib import contextmanager

import jax.numpy as jnp
import numpy as np

from ..obs import trace
from ..runtime import x64
from .batch_cost import (PartSpec, _batch_cost, _next_pow2, _prep_configs,
                         _prep_specs, _t_bucket)
from .jit_registry import register_jits

_STATE = threading.local()


def overlap_enabled() -> bool:
    """True unless the calling thread is inside :func:`serial_dispatch`."""
    return getattr(_STATE, "serial", 0) == 0


@contextmanager
def serial_dispatch():
    """Force dispatches on this thread to resolve at the dispatch site."""
    _STATE.serial = getattr(_STATE, "serial", 0) + 1
    try:
        yield
    finally:
        _STATE.serial -= 1


#: no jit of its own: the dispatches go through ``batch_cost._batch_cost``
_JITTED = register_jits()


class PendingPairedCost:
    """In-flight latency row of one paired sweep; resolve once, late."""

    __slots__ = ("n", "_parts", "_freq", "_row")

    def __init__(self, n: int, parts: list, freq: float):
        self.n = n
        self._parts = parts
        self._freq = freq
        self._row: np.ndarray | None = None

    @property
    def resolved(self) -> bool:
        return self._row is not None

    @property
    def ready(self) -> bool:
        """True when pulling the row would no longer block (non-blocking)."""
        if self._row is not None:
            return True
        return all(dev.is_ready() for _, dev, _ in self._parts)

    def latency_row(self) -> np.ndarray:
        """Block on the device rows (once) and return ``[n]`` seconds."""
        if self._row is None:
            out = np.empty(self.n, np.float64)
            with trace.span("device_wait", cat="engine", what="batch_cost"):
                for idxs, dev, n_real in self._parts:
                    out[idxs] = np.asarray(dev)[0, :n_real] / self._freq
            self._row = out
            self._parts = None
        return self._row


def _dispatch_block(configs, specs, idxs, t_pad, spec_chunk):
    """One ``_batch_cost`` leaf — same padding/programs as the serial path.

    Returns the pending part and the bytes of host arrays it handed over.
    """
    n_real = len(specs)
    n_pad = min(spec_chunk, _next_pow2(max(128, n_real)))
    if n_pad > n_real:
        configs = configs + [configs[-1]] * (n_pad - n_real)
        specs = specs + [specs[-1]] * (n_pad - n_real)
    lay_np, t = _prep_specs(specs, t_pad=t_pad)
    cfg_np, cons = _prep_configs(configs)
    with x64():
        lay = {k: jnp.asarray(v) for k, v in lay_np.items()}
        cfg = {k: jnp.asarray(v) for k, v in cfg_np.items()}
        res = _batch_cost(cfg, lay, t_pad=t, data_bits=cons.data_bits,
                          psum_bits=cons.psum_bits,
                          dram_row_miss=cons.dram_row_miss_cycles,
                          paired=True)
    nbytes = sum(v.nbytes for v in (*lay_np.values(), *cfg_np.values()))
    return (idxs, res["total_cycles"], n_real), nbytes


def dispatch_paired_latency(configs, specs, *, spec_chunk: int = 1024
                            ) -> PendingPairedCost:
    """Async twin of ``batch_part_cost_paired(...).latency_s[0]``.

    Enqueues the same (T-bucket, pair-block) programs on the same inputs
    and returns a :class:`PendingPairedCost` of device rows.  Under
    :func:`serial_dispatch` the pending resolves immediately, reproducing
    the sync-at-dispatch behaviour of the serial path.
    """
    specs = [s if isinstance(s, PartSpec) else PartSpec(*s) for s in specs]
    configs = list(configs)
    if len(configs) != len(specs):
        raise ValueError("paired costing needs len(configs) == len(specs)")
    if not specs:
        raise ValueError("need at least one (config, spec) pair")
    buckets: dict[int, list[int]] = {}
    for i, s in enumerate(specs):
        buckets.setdefault(_t_bucket(s.layer), []).append(i)
    parts, nbytes = [], 0
    with trace.span("dispatch_paired", cat="engine",
                    pairs=len(specs), buckets=len(buckets)) as sp:
        for tb in sorted(buckets):
            idxs = buckets[tb]
            for s in range(0, len(idxs), spec_chunk):
                blk = idxs[s:s + spec_chunk]
                part, n = _dispatch_block(
                    [configs[i] for i in blk], [specs[i] for i in blk],
                    np.asarray(blk, np.intp), tb, spec_chunk)
                parts.append(part)
                nbytes += n
        sp["bytes"] = nbytes
    pending = PendingPairedCost(len(specs), parts, configs[0].cons.freq_hz)
    if not overlap_enabled():
        pending.latency_row()
    return pending


class OverlapExecutor:
    """Interleave dispatch-phase generators with deferred resolve work.

    ``drive(gen)`` exhausts a phase generator, advancing one deferred
    generator step at each yield (each yield marks "device work just
    went in flight — now is the time for host work").  ``defer(gen)``
    queues follow-up host work; deferred generators run strictly FIFO,
    each exhausted before the next starts, so any order-sensitive
    accumulation they perform matches the serial schedule exactly.
    """

    def __init__(self, *, enabled: bool = True):
        self.enabled = enabled
        self._queue: deque = deque()

    def drive(self, gen):
        """Run ``gen`` to completion; returns its ``return`` value.

        When a yield hands back a pending (anything with a ``ready``
        property), deferred work keeps stepping until the pending's
        device rows are ready — the generator never waits on the device
        while host work is queued, and extra steps cannot reorder
        anything (deferred generators are strictly FIFO either way).
        """
        while True:
            try:
                pending = next(gen)
            except StopIteration as stop:
                return stop.value
            if self.enabled:
                self.step()
                while (self._queue and pending is not None
                       and not pending.ready):
                    self.step()

    def defer(self, gen) -> None:
        """Queue a generator of host work; runs inline when disabled."""
        if not self.enabled:
            for _ in gen:
                pass
            return
        self._queue.append(gen)

    def step(self) -> bool:
        """Advance the oldest deferred generator by one yield."""
        if not self._queue:
            return False
        try:
            next(self._queue[0])
        except StopIteration:
            self._queue.popleft()
        return True

    def drain(self) -> None:
        """Exhaust every deferred generator (the observation boundary)."""
        if not self._queue:
            return
        with trace.span("overlap_drain", cat="engine",
                        pending=len(self._queue)):
            while self._queue:
                self.step()


__all__ = ["OverlapExecutor", "PendingPairedCost", "dispatch_paired_latency",
           "overlap_enabled", "serial_dispatch"]
