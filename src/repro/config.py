"""Runtime dispatch overrides: the ONE home for the knobs that steer the
multiply / division / modexp dispatchers and the autotune sweep.

``repro.api.configure(...)`` writes here (process-wide, or scoped with
its context-manager form); the legacy ``REPRO_*`` environment variables
keep working as DEPRECATED aliases -- one DeprecationWarning per
variable per process -- at lower precedence than ``configure()``.

Precedence, highest first:

  1. ``repro.api.configure(...)`` values,
  2. the deprecated env vars (``REPRO_MUL_BACKEND`` /
     ``REPRO_DIV_BACKEND`` / ``REPRO_MODEXP_BACKEND`` /
     ``REPRO_AUTOTUNE``),
  3. the size/batch dispatch heuristics in ``configs/dot_bignum.py``
     (consulted by the ``select_*`` functions when ``resolve`` returns
     None).

This module is import-light on purpose (stdlib only): the core modules
consult it from inside their dispatch functions, and nothing here may
pull jax or the kernel packages into the import graph.
"""
from __future__ import annotations

import os
import warnings

OVERRIDE_NAMES = ("mul_method", "div_method", "modexp_backend", "autotune",
                  "ntt_cache_entries", "observability", "on_retrace",
                  "selfcheck", "kernel_fallback")

# ntt_cache_entries / observability / on_retrace / selfcheck /
# kernel_fallback have no env aliases: they never existed as REPRO_*
# vars, so there is no legacy spelling to keep working.
# ``observability`` is the repro.obs master switch (dispatch trace +
# spans + engine metric ticking); ``on_retrace`` picks the
# retrace-alarm policy ("ignore" / "warn" / "raise", see
# repro/obs/retrace.py -- the retrace COUNTER ticks regardless);
# ``selfcheck`` arms residue/witness result verification (None/False
# off, "warn" / "raise" policies, see repro/resilience/selfcheck.py);
# ``kernel_fallback`` gates degradation through the guarded kernel
# tiers and the serving engine (True degrades; None/False, the default,
# is strict -- the first failure propagates, see
# repro/resilience/guard.py).
ENV_ALIASES = {
    "mul_method": "REPRO_MUL_BACKEND",
    "div_method": "REPRO_DIV_BACKEND",
    "modexp_backend": "REPRO_MODEXP_BACKEND",
    "autotune": "REPRO_AUTOTUNE",
}

_overrides: dict = {name: None for name in OVERRIDE_NAMES}
_env_warned: set = set()


def get_override(name: str):
    """The configure() value for ``name`` (None: unset)."""
    return _overrides[name]


def set_overrides(updates: dict) -> dict:
    """Apply configure() values; returns the PREVIOUS values so the
    context-manager form can restore them.  A None value clears the
    override (dispatch falls back to env alias, then heuristics)."""
    prev = {}
    for name, value in updates.items():
        if name not in _overrides:
            raise TypeError(
                f"unknown configure() option {name!r}; choose from "
                f"{OVERRIDE_NAMES}")
        prev[name] = _overrides[name]
        _overrides[name] = value
    return prev


def _env_value(name: str):
    env_var = ENV_ALIASES.get(name)
    if env_var is None:
        return None
    raw = os.environ.get(env_var, "")
    if not raw:
        return None
    if env_var not in _env_warned:
        _env_warned.add(env_var)
        warnings.warn(
            f"{env_var} is deprecated; use repro.api.configure("
            f"{name}=...) (process-wide) or its context-manager form "
            f"(scoped) instead",
            DeprecationWarning, stacklevel=4)
    return raw


def resolve(name: str, valid=None, what: str = "value"):
    """The active override for ``name``: configure() first, then the
    deprecated env alias; None when neither is set (caller falls back
    to its heuristics).  ``valid`` checks membership and raises the
    repo-standard "unknown ...; choose from ..." error, naming the
    source so a stale env var is identifiable from the message."""
    value = _overrides[name]
    src = f"repro.api.configure({name}=...)"
    if value is None:
        value = _env_value(name)
        src = ENV_ALIASES.get(name, src)
    if value is None:
        return None
    if valid is not None and value not in valid:
        raise ValueError(
            f"unknown {what} {value!r} (via {src}); choose from {valid}")
    return value


def autotune_enabled() -> bool:
    """The autotune knob: configure(autotune=...) wins; the deprecated
    REPRO_AUTOTUNE env var parses as a boolean string; default off."""
    value = resolve("autotune")
    if value is None:
        return False
    if isinstance(value, bool):
        return value
    return str(value).lower() not in ("", "0", "false", "off")
