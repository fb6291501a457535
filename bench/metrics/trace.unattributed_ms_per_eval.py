"""Traced wall in no phase: ms per evaluation.

The traced window's time on the campaign loop's thread with no program
span open, or only a container (``attribution.CONTAINERS``: ``iteration``,
``evaluate``, ``map_wave``, ``map_many``, ``map``, ``overlap_drain``).
With the phase readers and the tuner's spans it sums to the traced wall
per evaluation.
"""

import attribution


def read(ctx):
    return attribution.unattributed_ms_per_eval(ctx)
