"""Data-Scheduler, host side: ms per evaluation, innermost in its spans.

``sched_problems`` (sharing-problem extraction), ``prefill_schedules``,
``schedule_many`` and ``schedule`` (bucket set-up, packing, the search's
dispatch), by innermost-span attribution (``bench/attribution.py``); the
waits on the search are ``device.wait_ms_per_eval``.
"""

import attribution

NAMES = ("sched_problems", "prefill_schedules", "schedule_many", "schedule")


def read(ctx):
    return attribution.ms_per_eval(ctx, NAMES)
