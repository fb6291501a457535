"""Mapper DL pass: ms per evaluation, innermost in its spans.

``dl_dispatch`` (the layout sweep's specs and their dispatch) and
``dl_optimize`` (the sweep's table and ``_optimize_dl``), by
innermost-span attribution (``bench/attribution.py``).
"""

import attribution

NAMES = ("dl_dispatch", "dl_optimize")


def read(ctx):
    return attribution.ms_per_eval(ctx, NAMES)
