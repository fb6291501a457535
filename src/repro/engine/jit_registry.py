"""Creation-time registration of module-level jitted programs.

Every engine module exposes a ``_JITTED`` dict mapping a stable label to
its jitted callables so ``engine.engine_program_counts()`` can report
compiled-program counts (retrace detection) and pimlint's PIM002 rule can
verify nothing jitted escapes the registry.  Before this helper, a new jit
had to be added to the dict *post hoc* — easy to forget, and PIM002 only
caught the omission after the fact.

``register_jits`` builds the registry at jit-creation time::

    _fold_keys = jax.jit(...)
    _JITTED = register_jits(fold_keys=_fold_keys)

The keyword-argument form keeps the callables visible as names in the
``_JITTED = ...`` assignment, which is exactly what PIM002's registry scan
reads — so registration and lint-visibility are one act, not two.

``register_jit`` covers the lazy case (programs specialized at first use,
e.g. per-mesh-size wave kernels): it inserts into an existing registry and
returns the function so the call can wrap the ``jax.jit`` site directly.
"""

from __future__ import annotations

from typing import Callable


def register_jits(**jits: Callable) -> dict[str, Callable]:
    """Build a module ``_JITTED`` registry from keyword-named jits."""
    for name, fn in jits.items():
        if not callable(fn):
            raise TypeError(f"jit registry entry {name!r} is not callable")
    return dict(jits)


def register_jit(registry: dict[str, Callable], name: str,
                 fn: Callable) -> Callable:
    """Insert one lazily-created jit into ``registry`` and return it."""
    if not callable(fn):
        raise TypeError(f"jit registry entry {name!r} is not callable")
    registry[name] = fn
    return fn


__all__ = ["register_jits", "register_jit"]
