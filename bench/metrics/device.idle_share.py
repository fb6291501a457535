"""Device idle share of the traced window, in percent.

One minus the union of device program intervals over the traced window
(the window's first iterations, ``run.TRACE_SECONDS``), from
the profiler trace, averaged over the chips traced.
"""

import tracing


def read(ctx):
    if not ctx["device"] or ctx["trace_window_s"] <= 0:
        return None
    busy = [tracing.busy_ns(evs) / 1e9 for evs in ctx["device"].values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / ctx["trace_window_s"])
