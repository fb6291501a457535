"""The one place that decides precision, kernel mode and the compile cache.

``repro.runtime`` holds the x64 helper, the native-vs-interpret kernel
decision and the compile-cache setup.  These tests pin that it stays the
only place: no other module enters x64 or asks for the backend, nothing
decides the backend while modules are imported, and asking for interpret
mode on a TPU backend is an error (steered here by patching
:func:`repro.runtime.backend`; the tests themselves run on the CPU).
"""

import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import runtime

ROOT = Path(__file__).resolve().parents[1]
RUNTIME = ROOT / "src" / "repro" / "runtime.py"


def _python_files():
    files = [ROOT / "chip_smoke.py"]
    for d in ("src", "benchmarks", "examples", "tests"):
        files += sorted((ROOT / d).rglob("*.py"))
    return [f for f in files if f != Path(__file__).resolve()]


def _offenders(pattern: str, allowed=(RUNTIME,)):
    rx = re.compile(pattern)
    return [f"{f.relative_to(ROOT)}:{i}"
            for f in _python_files() if f not in allowed
            for i, line in enumerate(f.read_text().splitlines(), 1)
            if rx.search(line)]


@pytest.mark.parametrize("what,pattern", [
    ("x64 scope", r"enable_x64|jax_enable_x64"),
    ("removed jax.experimental API",
     r"jax\.experimental(\.| import ).*\b(enable_x64|shard_map)\b"),
    ("backend decision", r"default_backend\(\)"),
])
def test_only_runtime_decides(what, pattern):
    """x64 is entered, and the backend asked, only in ``repro.runtime``."""
    assert _offenders(pattern) == [], f"{what} outside repro/runtime.py"


def test_import_initializes_no_backend():
    """Importing the program decides nothing: no backend is brought up."""
    code = (
        "import repro.core.dse, repro.core.surrogates, repro.core.tuner\n"
        "import repro.engine, repro.kernels.dse_eval, repro.kernels.ops\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, list(xla_bridge._backends)\n")
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_interpret_follows_backend(monkeypatch):
    assert runtime.kernel_mode() == "interpret"     # the CPU test backend
    assert runtime.resolve_interpret(None) is True
    monkeypatch.setattr(runtime, "backend", lambda: "tpu")
    assert runtime.native_kernels()
    assert runtime.kernel_mode() == "native"
    assert runtime.resolve_interpret(None) is False
    assert runtime.resolve_interpret(False) is False


def test_interpret_on_tpu_raises(monkeypatch):
    monkeypatch.setattr(runtime, "backend", lambda: "tpu")
    with pytest.raises(ValueError, match="interpret=True on a TPU"):
        runtime.resolve_interpret(True)


def test_kernel_wrapper_refuses_interpret_on_tpu(monkeypatch):
    """The kernels ask the same question: no quiet interpreter on a chip."""
    from repro.kernels import dse_eval
    monkeypatch.setattr(runtime, "backend", lambda: "tpu")
    base = np.zeros((2, 8), np.float32)
    cnt = np.zeros((2, 4, 8), np.int16)
    with pytest.raises(ValueError, match="interpret=True on a TPU"):
        dse_eval.delta_maxload_rows(base, cnt, interpret=True)


def test_x64_scope_is_thread_local_f64():
    import jax.numpy as jnp
    assert jnp.asarray(np.zeros(2)).dtype == jnp.float32
    with runtime.x64():
        assert jnp.asarray(np.zeros(2)).dtype == jnp.float64
    assert jnp.asarray(np.zeros(2)).dtype == jnp.float32


@pytest.fixture
def cache_config():
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)


def test_compile_cache_fixed_path_in_checkout(monkeypatch, tmp_path,
                                              cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = runtime.configure_compile_cache(tmp_path)
    assert got == str(tmp_path.resolve() / runtime.CACHE_DIRNAME)
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    # the same checkout always gets the same directory (part of the key)
    assert runtime.configure_compile_cache(tmp_path) == got


def test_compile_cache_env_dir_wins(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "env"))
    assert runtime.configure_compile_cache(tmp_path / "repo") == \
        str(tmp_path / "env")
