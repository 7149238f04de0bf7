"""kernels/common/runtime: the compile-cache rule of the entry points.

``jax.config.update`` is replaced by a recorder, so these tests never
turn the cache on for the process."""
import pathlib

import pytest

from repro.kernels.common import runtime


@pytest.fixture
def updates(monkeypatch):
    calls = []
    monkeypatch.setattr(runtime.jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_compile_cache_env_dir_is_left_to_jax(monkeypatch, updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert runtime.use_compile_cache() == "/elsewhere/cache"
    assert updates == []


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch, updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = runtime.use_compile_cache()
    root = pathlib.Path(__file__).resolve().parent.parent
    assert path == str(root / ".jax_cache")
    assert updates == [("jax_compilation_cache_dir", path)]
    assert runtime.use_compile_cache() == path      # fixed, not per call
