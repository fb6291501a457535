"""Host waiting on the device: ms per evaluation, innermost in
``device_wait``.

The blocking pulls of costing rows (``what="batch_cost"``), scheduler keys
(``fold_keys``) and scheduler results (``scan_solve``), by innermost-span
attribution (``bench/attribution.py``).
"""

import attribution

NAMES = ("device_wait",)


def read(ctx):
    return attribution.ms_per_eval(ctx, NAMES)
