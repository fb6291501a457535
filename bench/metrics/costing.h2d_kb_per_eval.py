"""Device costing: KB of host arrays handed to the device per evaluation.

Sum of the ``bytes`` argument of the ``dispatch_paired`` spans (the nbytes
of every host array one paired dispatch copies to the device, the pow2
pair padding included), in KB of 1,000 bytes.  None where the program's
spans carry no such argument.
"""

import attribution


def read(ctx):
    total = attribution.arg_per_eval(ctx, "dispatch_paired", "bytes")
    return None if total is None else total / 1e3
