"""Programs compiled or loaded from the persistent cache inside the window.

Backend compiles plus persistent-cache hits, counted through
``jax.monitoring`` between the window's first and last iteration
boundary.  A warm-up that covers the window reads 0.
"""


def read(ctx):
    return ctx["compiles_in_window"]
