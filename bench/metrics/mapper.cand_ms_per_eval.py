"""Mapper candidate tables: ms per evaluation, innermost in their spans.

``cand_dispatch`` (key enumeration, ``_cand_struct``, dispatch of the
missing tables' node costs) and ``cand_build`` (the tables' construction
once the costs are back), by innermost-span attribution
(``bench/attribution.py``).
"""

import attribution

NAMES = ("cand_dispatch", "cand_build")


def read(ctx):
    return attribution.ms_per_eval(ctx, NAMES)
