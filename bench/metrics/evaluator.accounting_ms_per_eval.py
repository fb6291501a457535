"""Evaluator accounting: ms per evaluation, innermost in ``accounting``.

Each ``evaluate_mapping`` call: the scalar per-layer cost and the
memoized sharing latency of every heavy layer, by innermost-span
attribution (``bench/attribution.py``).
"""

import attribution

NAMES = ("accounting",)


def read(ctx):
    return attribution.ms_per_eval(ctx, NAMES)
