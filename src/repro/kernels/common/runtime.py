"""Backend/runtime helpers shared by every kernel ops wrapper."""
from __future__ import annotations

import os
import pathlib

import jax

# src/repro/kernels/common/runtime.py -> the checkout root
CHECKOUT = pathlib.Path(__file__).resolve().parents[4]


def auto_interpret(interpret: bool | None) -> bool:
    """Resolve the interpret flag: explicit value wins, else interpret
    mode on CPU (bit-exact kernel validation) and compiled on TPU."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache`` (the path is part of the cache's key, so
    it is never a temporary or per-process one).  Entry points call
    this; library code and the tests never do."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
