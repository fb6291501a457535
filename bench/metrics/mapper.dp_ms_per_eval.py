"""Mapper DP: ms per evaluation, innermost in ``dp_solve``.

The per-config Algorithm-2 solve (``RegionTable``, ``minplus_convolve``,
backtrack), by innermost-span attribution (``bench/attribution.py``).
"""

import attribution

NAMES = ("dp_solve",)


def read(ctx):
    return attribution.ms_per_eval(ctx, NAMES)
