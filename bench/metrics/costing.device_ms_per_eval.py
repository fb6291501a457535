"""Device costing: device ms of the ``_batch_cost`` programs per evaluation.

Summed durations of the device program events whose name carries
``_batch_cost`` inside the traced window, per evaluation made in it,
averaged over the chips traced.
"""

import tracing


def read(ctx):
    if not ctx["evaluations"] or not ctx["device"]:
        return None
    per_chip = [tracing.program_seconds(evs, lambda n: "_batch_cost" in n)
                for evs in ctx["device"].values()]
    total = sum(per_chip) / len(per_chip)
    if total == 0:
        return None
    return 1e3 * total / ctx["evaluations"]
