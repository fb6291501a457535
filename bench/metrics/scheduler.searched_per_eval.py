"""Sharing problems sent to the device search per evaluation.

Sum of the ``problems`` argument of the ``schedule`` spans (one per
bucket); problems resolved on the host never reach a bucket.
"""

import attribution


def read(ctx):
    return attribution.arg_per_eval(ctx, "schedule", "problems")
