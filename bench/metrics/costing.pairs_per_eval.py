"""(config, spec) pairs costed on the device per evaluation.

Sum of the ``pairs`` argument of the ``dispatch_paired`` spans: the pairs
missing from the node-latency memo, without the pow2 bucket's padding.
"""

import attribution


def read(ctx):
    return attribution.arg_per_eval(ctx, "dispatch_paired", "pairs")
