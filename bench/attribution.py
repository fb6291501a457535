"""Innermost-span attribution of the traced window.

Each instant of the traced window on the thread that runs the campaign
loop is charged to the innermost program span open at that instant.  The
per-phase readers in ``bench/metrics`` sum that time over span names, so
their numbers, the other spans' and the unattributed rest (no span open,
or only a container) partition the window.

``spans`` are the program's Chrome-format events (``ts``/``dur`` in
microseconds, one ``tid`` per thread), as ``ctx["spans"]`` holds them.
"""

from __future__ import annotations

import tracing

#: spans that only group other work: time in them and in no inner span is
#: host work no phase span covers yet
CONTAINERS = frozenset({"iteration", "evaluate", "map_wave", "map_many",
                        "map", "overlap_drain"})


def loop_thread(spans: list[dict]):
    """The ``tid`` of the thread that runs the campaign loop, or None."""
    for s in spans:
        if s["name"] == "iteration":
            return s["tid"]
    return spans[0]["tid"] if spans else None


def innermost_s(spans: list[dict]) -> dict[str, float]:
    """Seconds each span name was the innermost open span, loop thread only.

    Spans of one thread nest; a child that ends past its parent (rounding
    of the microsecond stamps) is clipped to the parent.
    """
    tid = loop_thread(spans)
    mine = sorted(((s["ts"], s["ts"] + s["dur"], s["name"]) for s in spans
                   if s["tid"] == tid), key=lambda s: (s[0], -s[1]))
    out: dict[str, float] = {}
    stack: list[tuple[float, str]] = []
    now = 0.0

    def charge(name, until):
        out[name] = out.get(name, 0.0) + (until - now)

    for a, b, name in mine:
        while stack and stack[-1][0] <= a:
            end, top = stack.pop()
            charge(top, end)
            now = end
        if stack:
            charge(stack[-1][1], a)
            b = min(b, stack[-1][0])
        now = a
        stack.append((b, name))
    while stack:
        end, top = stack.pop()
        charge(top, end)
        now = end
    return {k: v / 1e6 for k, v in out.items()}


def covered_s(spans: list[dict]) -> float:
    """Seconds the loop thread had any span open (the union of its spans)."""
    tid = loop_thread(spans)
    return sum(b - a for a, b in tracing.union(
        (s["ts"], s["ts"] + s["dur"]) for s in spans
        if s["tid"] == tid)) / 1e6


def ms_per_eval(ctx: dict, names) -> float | None:
    """Innermost ms per traced evaluation of the spans named in ``names``;
    None where no such span was recorded."""
    if not ctx["evaluations"]:
        return None
    inner = innermost_s(ctx["spans"])
    if not any(n in inner for n in names):
        return None
    return 1e3 * sum(inner.get(n, 0.0) for n in names) / ctx["evaluations"]


def unattributed_ms_per_eval(ctx: dict) -> float | None:
    """Traced ms per evaluation with no span open, or only a container."""
    if not ctx["evaluations"] or ctx["trace_window_s"] <= 0:
        return None
    inner = innermost_s(ctx["spans"])
    rest = ctx["trace_window_s"] - covered_s(ctx["spans"]) \
        + sum(v for k, v in inner.items() if k in CONTAINERS)
    return 1e3 * rest / ctx["evaluations"]


def arg_per_eval(ctx: dict, name: str, arg: str) -> float | None:
    """Sum of span ``name``'s argument ``arg`` per traced evaluation."""
    if not ctx["evaluations"]:
        return None
    vals = [s["args"][arg] for s in ctx["spans"]
            if s["name"] == name and arg in s["args"]]
    if not vals:
        return None
    return sum(vals) / ctx["evaluations"]
