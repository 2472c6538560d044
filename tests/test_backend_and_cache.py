"""Where the program runs: Pallas backend selection and the compile cache."""
from pathlib import Path

import jax
import pytest

from repro.compile_cache import DEFAULT_CACHE_DIR, enable_compile_cache
from repro.kernels.backend import resolve_interpret

ROOT = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize(
    "backend,expected", [("cpu", True), ("tpu", False), ("gpu", None)]
)
def test_resolve_interpret_by_backend(monkeypatch, backend, expected):
    """Compiled on a TPU, interpreted on the CPU, refused elsewhere (an
    interpreted kernel on an accelerator would serve at interpreter speed);
    an explicit flag always wins."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if expected is None:
        with pytest.raises(RuntimeError, match="gpu"):
            resolve_interpret(None)
    else:
        assert resolve_interpret(None) is expected
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


def test_compile_cache_honours_env(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/cache/from/env")
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == "/cache/from/env"
    assert jax.config.jax_compilation_cache_dir == before  # sets nothing


def test_compile_cache_default_is_ignored_dir_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(DEFAULT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert DEFAULT_CACHE_DIR.parent == ROOT
    assert f"{DEFAULT_CACHE_DIR.name}/" in (ROOT / ".gitignore").read_text().split()
