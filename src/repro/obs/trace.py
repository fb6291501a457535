"""Chrome-trace-format campaign tracing.

A :class:`Tracer` collects *complete* (``ph="X"``) trace events — one per
host-side span — into an in-memory list and serializes them as Chrome trace
event format JSON (load the file in ``chrome://tracing`` or Perfetto).  The
DSE stack is instrumented at three levels:

* **phase spans** (``cat="dse"``): ``iteration`` / ``propose`` / ``map`` /
  ``fit`` / ``evaluate`` / ``accounting`` / ``checkpoint`` emitted by
  ``run_dse`` / ``WorkloadEvaluator`` / ``Campaign``, one timeline row (tid)
  per strategy thread;
* **mapper phase spans** (``cat="mapper"``): ``cand_dispatch`` /
  ``cand_build`` (candidate tables), ``dp_solve`` (Algorithm 2) and
  ``dl_dispatch`` / ``dl_optimize`` (the DL pass), each closed before the
  phase generator yields;
* **engine spans** (``cat="engine"``): ``batch_cost``, ``dispatch_paired``,
  ``map_many`` / ``map_wave``, ``overlap_drain``, ``sched_problems``,
  ``prefill_schedules``, ``schedule_many`` / ``schedule``, ``fit_filter`` /
  ``fit_dkl``, ``score_candidates``, and ``device_wait`` around each
  blocking pull of device results.

Every span is also a :class:`jax.profiler.TraceAnnotation`, on the
profiler's clock: while ``jax.profiler.trace`` is active a span's ``ts``
and its annotation differ by one constant offset, so the host spans line
up with the XLA device trace.

Tracing is process-global and opt-in: :func:`install` (or the
:func:`activate` context manager) sets the active tracer; the module-level
:func:`span` helper is the single hot-path entry point and collapses to a
shared no-op context manager when no tracer is installed, so the disabled
path costs one global read + one singleton ``with`` per span site (the
decorator form: one global read and the plain call).  What tracing costs
when it is on is measured on the chip, and recorded in ``PERF.md``.

Span ``args`` carry the batch size / pow2 bucket key / cache outcome of the
dispatch; the context manager yields a mutable dict, so outcomes discovered
mid-span can be recorded::

    with span("evaluate", configs=4) as sp:
        sp["cache"] = "hit" if hit else "miss"
"""

from __future__ import annotations

import functools
import json
import threading
import time
from contextlib import contextmanager
from pathlib import Path

try:                                     # host/XLA span alignment is
    from jax.profiler import TraceAnnotation   # best-effort: tracing must
except Exception:                        # work on a jax-less interpreter
    TraceAnnotation = None

_PID = 1          # one "campaign" process row per trace


class _NullSpan:
    """Shared no-op span: the whole disabled-tracing hot path."""

    __slots__ = ()

    def __enter__(self):
        return {}

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class Tracer:
    """Thread-safe collector of Chrome trace events.

    Timestamps are microseconds from tracer creation (``perf_counter_ns``
    deltas — monotonic across threads).  Every emitting thread gets a
    stable small integer ``tid`` on first use; :meth:`set_thread_name`
    attaches the Chrome ``thread_name`` metadata record (the campaign names
    each strategy thread after its strategy).
    """

    def __init__(self):
        self._t0_ns = time.perf_counter_ns()
        self._events: list[dict] = []
        self._lock = threading.Lock()
        self._tids: dict[int, int] = {}
        self._local = threading.local()
        self._meta("process_name", {"name": "campaign"})

    # -- event plumbing ------------------------------------------------------
    def _now_us(self) -> float:
        return (time.perf_counter_ns() - self._t0_ns) / 1e3

    def _tid(self) -> int:
        tid = getattr(self._local, "tid", None)
        if tid is None:
            ident = threading.get_ident()
            with self._lock:
                tid = self._tids.setdefault(ident, len(self._tids) + 1)
            self._local.tid = tid
        return tid

    def _meta(self, name: str, args: dict, tid: int | None = None) -> None:
        ev = {"name": name, "ph": "M", "pid": _PID, "args": args}
        if tid is not None:
            ev["tid"] = tid
        with self._lock:
            self._events.append(ev)

    def set_thread_name(self, name: str) -> None:
        """Label the calling thread's timeline row (e.g. ``strategy:gp``)."""
        self._meta("thread_name", {"name": name}, tid=self._tid())

    @contextmanager
    def span(self, name: str, cat: str = "dse", **args):
        """Record one complete (``X``) event around the body.

        Yields the ``args`` dict — mutate it to attach outcomes (cache
        hit/miss, bucket keys) discovered while the span is open.
        """
        ann = TraceAnnotation(name) if TraceAnnotation is not None else None
        if ann is not None:
            ann.__enter__()
        # the span's edges sit right inside the annotation's, so the two
        # differ by the clocks' offset alone
        t0 = self._now_us()
        try:
            yield args
        finally:
            t1 = self._now_us()
            if ann is not None:
                ann.__exit__(None, None, None)
            ev = {"name": name, "cat": cat, "ph": "X", "ts": t0,
                  "dur": t1 - t0, "pid": _PID, "tid": self._tid(),
                  "args": args}
            with self._lock:
                self._events.append(ev)

    def instant(self, name: str, cat: str = "dse", **args) -> None:
        """Record an instant (``i``) event — warnings, one-shot markers."""
        ev = {"name": name, "cat": cat, "ph": "i", "ts": self._now_us(),
              "pid": _PID, "tid": self._tid(), "s": "t", "args": args}
        with self._lock:
            self._events.append(ev)

    # -- export --------------------------------------------------------------
    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def to_chrome(self) -> dict:
        """Chrome trace event format object (metadata first, spans by ts).

        ``otherData.origin_perf_counter_ns`` is the tracer's zero on the
        host's monotonic clock.  Every span is also a profiler annotation,
        so one span found in both traces gives the single offset that lays
        this file over a ``jax.profiler`` device trace.
        """
        evs = self.events()
        meta = [e for e in evs if e["ph"] == "M"]
        rest = sorted((e for e in evs if e["ph"] != "M"),
                      key=lambda e: e["ts"])
        return {"traceEvents": meta + rest, "displayTimeUnit": "ms",
                "otherData": {"origin_perf_counter_ns": self._t0_ns}}

    def save(self, path: str | Path) -> Path:
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(self.to_chrome()))
        return path


# ---------------------------------------------------------------------------
# Process-global active tracer
# ---------------------------------------------------------------------------

_ACTIVE: Tracer | None = None


def install(tracer: Tracer | None) -> None:
    """Set (or with ``None`` clear) the process-global active tracer."""
    global _ACTIVE
    _ACTIVE = tracer


def current() -> Tracer | None:
    return _ACTIVE


@contextmanager
def activate(tracer: Tracer):
    """Install ``tracer`` for the block, restoring the previous one after."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = tracer
    try:
        yield tracer
    finally:
        _ACTIVE = prev


def span(name: str, cat: str = "dse", **args):
    """Span on the active tracer; the shared no-op when tracing is off."""
    t = _ACTIVE
    if t is None:
        return _NULL_SPAN
    return t.span(name, cat=cat, **args)


def instant(name: str, cat: str = "dse", **args) -> None:
    t = _ACTIVE
    if t is not None:
        t.instant(name, cat=cat, **args)


def set_thread_name(name: str) -> None:
    t = _ACTIVE
    if t is not None:
        t.set_thread_name(name)


def traced(name: str, cat: str = "engine", argspec=None):
    """Decorator form of :func:`span` for engine dispatch sites.

    ``argspec(*a, **kw)`` (optional) builds the span args from the call's
    arguments.  The disabled path is one global check + the undecorated
    call — nothing is built or allocated.
    """
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*a, **kw):
            t = _ACTIVE
            if t is None:
                return fn(*a, **kw)
            args = argspec(*a, **kw) if argspec is not None else {}
            with t.span(name, cat=cat, **args):
                return fn(*a, **kw)
        return wrapper
    return deco
