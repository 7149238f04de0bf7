"""Guarded tiered execution: every Pallas backend entry runs through
here so a compile / VMEM / lowering failure degrades to the next tier
instead of killing the request.

``run(op, nbits, tiers)`` walks an ordered list of (backend, thunk)
tiers -- conventionally ``pallas -> jnp -> reference`` -- and returns
the first success:

  * a tier whose breaker key (op, shape-bucket, backend) is open is
    skipped outright, ticking ``fallback_total{reason="quarantined"}``
    (no failed-compile latency paid per request while quarantined);
  * a tier that raises opens its breaker key, ticks
    ``fallback_total{op,backend,reason}`` with the classified failure,
    and falls through to the next tier;
  * the FINAL tier is the correctness anchor: it is never skipped by
    the breaker and its exceptions propagate (there is nothing left to
    fall back to).

Fall-through is OFF by default (strict mode: the first failure
propagates), so a kernel that fails to lower on the device is an error
and never a silent run of the jnp composition.  A caller that prefers
an answer at any cost opts in with
``repro.api.configure(kernel_fallback=True)``; quarantine skipping
applies either way, because a forced-open breaker is an explicit
operator decision.

The guard runs at trace time inside jit (core dispatchers call it while
XLA is tracing), which is exactly where Pallas compile and lowering
failures surface; the ``fallback_total`` ticks are therefore per-trace,
not per-call -- matching the dispatch-trace semantics of PR 8, and
matching ``inject.log()`` one-to-one for the chaos gates.  Like
``retraces_total``, the counter ticks even with observability off.
"""
from __future__ import annotations

from typing import Callable, List, Tuple

from repro import config as _config
from repro.obs import metrics as _metrics
from repro.resilience import inject as _inject
from repro.resilience.breaker import BREAKER

METRIC = "fallback_total"

_HELP = "kernel-tier fallbacks by op/backend/reason"


def fallback_enabled() -> bool:
    """configure(kernel_fallback=...): True -> degrade through the
    tiers; None/False (the default) -> strict mode (first failure
    propagates)."""
    return bool(_config.get_override("kernel_fallback"))


def classify(exc: BaseException) -> str:
    """Coarse failure-reason label for ``fallback_total`` (stable label
    set: cardinality-bounded, greppable in metrics artifacts)."""
    if isinstance(exc, _inject.InjectedFault):
        return "injected"
    msg = f"{type(exc).__name__}: {exc}".lower()
    if "resource_exhausted" in msg or "resource exhausted" in msg \
            or "out of memory" in msg or "vmem" in msg:
        return "oom"
    if "lower" in msg or "mosaic" in msg or "unsupported" in msg \
            or "not implemented" in msg or "notimplemented" in msg:
        return "lowering"
    if "compil" in msg:
        return "compile"
    return type(exc).__name__


def tick(op: str, backend: str, reason: str, amount: int = 1) -> None:
    """Public tick for callers with their own fallback logic (the
    serving engine's flush degradation / selfcheck repair)."""
    _metrics.REGISTRY.counter(METRIC, _HELP).inc(
        amount, op=op, backend=backend, reason=reason)


def run(op: str, nbits: int, tiers: List[Tuple[str, Callable]]):
    """Execute the first healthy tier; degrade on failure (see module
    docstring).  ``tiers`` is ordered fastest-first; the last entry must
    be infallible-by-construction (jnp composition or host reference)."""
    last_exc: BaseException | None = None
    final = len(tiers) - 1
    for i, (backend, thunk) in enumerate(tiers):
        if i < final and not BREAKER.allow(op, nbits, backend):
            tick(op, backend, "quarantined")
            continue
        try:
            _inject.fire(f"{op}/{backend}")
            out = thunk()
        except Exception as exc:                    # noqa: BLE001
            if i == final:
                raise
            BREAKER.record_failure(op, nbits, backend)
            tick(op, backend, classify(exc))
            last_exc = exc
            if not fallback_enabled():
                raise
            continue
        BREAKER.record_success(op, nbits, backend)
        return out
    # unreachable unless tiers was empty (the final tier either
    # returned or raised)
    raise last_exc if last_exc is not None else ValueError(
        f"guard.run: no tiers given for op {op!r}")
