"""From the profiler's trace and the program's spans to per-layer numbers.

The profiler writes an ``.xplane.pb``; :func:`load_events` flattens it into
``{"plane", "line", "name", "start", "dur"}`` rows (nanoseconds, one clock
for host annotations and device programs).  Everything else here works on
such rows, so the reduction can be checked on a small recorded trace.
"""

from __future__ import annotations

from pathlib import Path

DEVICE_PREFIX = "/device:TPU:"
MODULE_LINE = "XLA Modules"
WINDOW = "bench_window"


def load_events(trace_dir: str | Path) -> list[dict]:
    import jax
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        return []
    data = jax.profiler.ProfileData.from_file(str(files[-1]))
    rows = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name != MODULE_LINE:
                continue
            for ev in line.events:
                rows.append({"plane": plane.name, "line": line.name,
                             "name": ev.name, "start": int(ev.start_ns),
                             "dur": int(ev.duration_ns)})
    return rows


def window_bounds(rows: list[dict]) -> tuple[int, int] | None:
    for r in rows:
        if r["name"] == WINDOW and not r["plane"].startswith(DEVICE_PREFIX):
            return r["start"], r["start"] + r["dur"]
    return None


def device_rows(rows, lo: int, hi: int) -> dict[str, list[dict]]:
    """Device program events clipped to ``[lo, hi]``, per device plane."""
    out: dict[str, list[dict]] = {}
    for r in rows:
        if not r["plane"].startswith(DEVICE_PREFIX):
            continue
        a, b = max(lo, r["start"]), min(hi, r["start"] + r["dur"])
        if b > a:
            out.setdefault(r["plane"], []).append(
                {**r, "start": a, "dur": b - a})
    return out


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted ``(start, end)`` intervals."""
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def busy_ns(events) -> int:
    return sum(b - a for a, b in union((e["start"], e["start"] + e["dur"])
                                       for e in events))


def program_seconds(events, match) -> float:
    """Summed device seconds of the programs whose name ``match`` accepts."""
    return sum(e["dur"] for e in events if match(e["name"])) / 1e9


def top_programs(events, n: int = 10) -> list[list]:
    tot: dict[str, int] = {}
    for e in events:
        key = e["name"].split("(")[0]
        tot[key] = tot.get(key, 0) + e["dur"]
    return [[k, v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(events, host_rows, lo: int, hi: int, n: int = 10
              ) -> list[list]:
    """Longest device-idle gaps in ``[lo, hi]``, named by the innermost
    host annotation open at the gap's midpoint."""
    busy = union((e["start"], e["start"] + e["dur"]) for e in events)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    spans = [r for r in host_rows if r["name"] != WINDOW]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (a + b) // 2
        open_ = [s for s in spans if s["start"] <= mid < s["start"] + s["dur"]]
        name = min(open_, key=lambda s: s["dur"])["name"] if open_ else "none"
        out.append([name, (b - a) / 1e9])
    return out


def self_time_s(spans: list[dict], parents: set, children: set) -> float:
    """Seconds inside ``parents`` spans not covered by ``children`` spans.

    ``spans`` are the program's Chrome-format events (``ts``/``dur`` in
    microseconds, one ``tid`` per thread).  Nested parents count once.
    """
    total = 0.0
    for tid in {s["tid"] for s in spans}:
        mine = [s for s in spans if s["tid"] == tid]
        par = union((s["ts"], s["ts"] + s["dur"]) for s in mine
                    if s["name"] in parents)
        kid = union((s["ts"], s["ts"] + s["dur"]) for s in mine
                    if s["name"] in children)
        for a, b in par:
            covered = sum(max(0.0, min(b, y) - max(a, x)) for x, y in kid)
            total += (b - a) - covered
    return total / 1e6


def span_union_s(spans: list[dict], names: set) -> float:
    """Seconds covered by spans named in ``names`` (overlaps counted once)."""
    total = 0.0
    for tid in {s["tid"] for s in spans}:
        total += sum(b - a for a, b in union(
            (s["ts"], s["ts"] + s["dur"]) for s in spans
            if s["tid"] == tid and s["name"] in names))
    return total / 1e6
