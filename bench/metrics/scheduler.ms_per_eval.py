"""Data-Scheduler: host wall ms per evaluation in scheduling spans.

Spans ``prefill_schedules`` (the cross-config batch), ``schedule_many``
and ``schedule`` (one bucket's jitted search), overlaps counted once; the
time includes waiting on the device search.
"""

import tracing

NAMES = {"prefill_schedules", "schedule_many", "schedule"}


def read(ctx):
    if not ctx["evaluations"]:
        return None
    return 1e3 * tracing.span_union_s(ctx["spans"], NAMES) \
        / ctx["evaluations"]
