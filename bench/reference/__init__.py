"""Plain reference of the NicePIM evaluation path, independent of ``repro``.

A straightforward host implementation of what one DSE observation is made
of: the area model, the per-node analytic cost model with its tiling search,
the layer partitions and their ring estimates, the slicing-tree segment
mappings, the capacity-constrained DP of Algorithm 2 with the alternated
data-layout pass, the XY-routed mesh NoC, and the link-load accounting of a
Data-Scheduler schedule.  It imports nothing of the program and takes
nothing the program has made except the schedules it checks.

Every floating-point quantity is computed in the NumPy dtype handed to the
entry points: ``float64`` is the reference, ``float32`` the lower-precision
control that the comparison must reject.
"""
