"""Tuner: host wall ms per window iteration in propose and fit spans.

Spans ``propose`` (staged path), ``fused_propose`` (dispatch of the fused
on-device chain), ``propose_resolve`` (its one host sync) and ``fit``
(both model refits, dispatched), overlaps counted once.
"""

import tracing

NAMES = {"propose", "fused_propose", "propose_resolve", "fit"}


def read(ctx):
    if not ctx["iterations"]:
        return None
    return 1e3 * tracing.span_union_s(ctx["spans"], NAMES) \
        / ctx["iterations"]
