"""Where the program runs, and in which precision: one module decides both.

* :func:`x64` is the one way into float64 math.  The batched cost model,
  the mapper's DP and the scheduler's exact gate run in f64 for 1e-6
  parity with the scalar reference (``core/costmodel.py``).  It wraps
  ``jax.enable_x64``, which is thread-local: every thread that needs f64
  (eval workers included) enters it itself.
* :func:`native_kernels` says whether Pallas kernels compile natively —
  on a TPU backend — or run in interpret mode, as they do on the CPU
  backend the tests use.  :func:`resolve_interpret` applies that decision
  to a kernel's ``interpret`` argument.  The backend is asked lazily, at
  call time: importing a module never initializes one.
* :func:`configure_compile_cache` places JAX's persistent compile cache
  for the entry points (``chip_smoke.py``, ``examples/dse_nicepim.py``,
  ``benchmarks/run.py``).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: cache directory inside the checkout, used when the environment names none
CACHE_DIRNAME = ".jax_cache"


def x64():
    """Context manager: float64/int64 arrays on the calling thread."""
    return jax.enable_x64(True)


def backend() -> str:
    """The default JAX backend (``"tpu"``, ``"cpu"``, ...)."""
    return jax.default_backend()


def native_kernels() -> bool:
    """True where Pallas kernels compile natively (a TPU backend)."""
    return backend() == "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """A kernel's interpret flag: native on a TPU, interpreted elsewhere.

    ``None`` takes the backend's mode.  Asking for interpret mode on a TPU
    backend is an error: the main path must never fall back to the
    interpreter on the chip.
    """
    native = native_kernels()
    if interpret is None:
        return not native
    if interpret and native:
        raise ValueError("interpret=True on a TPU backend: the Pallas "
                         "kernels compile natively there")
    return bool(interpret)


def kernel_mode() -> str:
    """``"native"`` or ``"interpret"``: how Pallas kernels run here."""
    return "native" if native_kernels() else "interpret"


def configure_compile_cache(root: str | os.PathLike) -> str:
    """Turn on JAX's persistent compile cache for an entry point.

    ``JAX_COMPILATION_CACHE_DIR``, where set, names the directory and JAX
    reads it itself; otherwise the cache goes to ``<root>/.jax_cache``, a
    fixed path (the path is part of the cache key, so it must not move
    between runs).  The engine compiles many small programs, most under
    JAX's default one-second threshold, so every program is cached.
    Returns the directory in use.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(Path(root).resolve() / CACHE_DIRNAME))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


__all__ = ["CACHE_DIRNAME", "backend", "configure_compile_cache",
           "kernel_mode", "native_kernels", "resolve_interpret", "x64"]
