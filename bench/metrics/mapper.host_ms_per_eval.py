"""Mapper host work: ms per evaluated design point.

Self time of the mapper spans (``map``, ``map_many``, ``map_wave``) less
the engine spans inside them: device costing dispatch (``batch_cost``,
``dispatch_paired``), the overlap drain, and the Data-Scheduler
(``schedule``, ``schedule_many``, ``prefill_schedules``).  What remains is
host candidate generation, the DP, the DL pass, waits on device rows, and
the deferred per-layer accounting the overlap executor runs in the
in-flight windows.
"""

import tracing

PARENTS = {"map", "map_many", "map_wave"}
CHILDREN = {"batch_cost", "dispatch_paired", "overlap_drain", "schedule",
            "schedule_many", "prefill_schedules"}


def read(ctx):
    if not ctx["evaluations"]:
        return None
    return 1e3 * tracing.self_time_s(ctx["spans"], PARENTS, CHILDREN) \
        / ctx["evaluations"]
