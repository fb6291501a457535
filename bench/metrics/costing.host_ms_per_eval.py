"""Device costing, host side: ms per evaluation, innermost in its spans.

``dispatch_paired`` (spec and config packing, pow2 padding, enqueue) and
``batch_cost`` (the synchronous costing entry points), by innermost-span
attribution (``bench/attribution.py``).  Every blocking pull of their
device rows, in ``PendingPairedCost.latency_row`` and inside the
synchronous entry points alike, is a ``device_wait`` span and so counts in
``device.wait_ms_per_eval`` instead.
"""

import attribution

NAMES = ("dispatch_paired", "batch_cost")


def read(ctx):
    return attribution.ms_per_eval(ctx, NAMES)
