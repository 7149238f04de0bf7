"""Dispatch tracing: structured events from the dispatch tier choosers.

``core/mul.select_method``, ``core/div.select_div_method``,
``core/modular.select_modexp_backend`` and ``configs/dot_bignum.
pick_modexp_window`` call ``emit(...)`` with the decision they just
made and WHICH threshold fired.  Events land in a bounded ring buffer
(and tick a ``dispatch_total`` counter in the metrics registry), so an
operator can ask "which backend did the 8192-bit batch-1 multiplies
actually take, and why" without the ``--show-dispatch`` print
statements this replaces.

Cost model: dispatch decisions happen at Python dispatch / jit-trace
time, never per element, and ``emit`` is a no-op unless observability
is on (``repro.api.configure(observability=True)``) -- the disabled
path is one dict lookup, no event object is ever allocated
(tests/test_obs.py asserts this via the buffer and counters).

Subscribers (``subscribe(fn)``) see each event as it is emitted --
the hook for streaming dispatch logs somewhere live.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Callable, List, Optional, Tuple

from repro import config as _config

CAPACITY = 1024                     # ring buffer: keeps the newest events

DISPATCHERS = ("mul", "div", "modexp", "modexp_window")


@dataclasses.dataclass(frozen=True)
class DispatchEvent:
    """One dispatch decision.  ``rule`` names the threshold that fired
    (e.g. "nbits<=vnc_max_bits(512)"), ``detail`` carries dispatcher-
    specific extras as sorted (key, value) pairs."""

    dispatcher: str
    nbits: int
    batch: int
    choice: str
    rule: str
    detail: Tuple[Tuple[str, object], ...] = ()


_events: deque = deque(maxlen=CAPACITY)
_subscribers: List[Callable[[DispatchEvent], None]] = []


def enabled() -> bool:
    """Observability master switch (configure(observability=True))."""
    return bool(_config.get_override("observability"))


def emit(dispatcher: str, nbits: int, batch: int, choice: str, rule: str,
         **detail) -> None:
    """Record one dispatch decision; no-op (and no allocation) when
    observability is off."""
    if not _config.get_override("observability"):
        return
    ev = DispatchEvent(dispatcher, int(nbits), int(batch), str(choice),
                       rule, tuple(sorted(detail.items())))
    _events.append(ev)
    from repro.obs import metrics as _m
    _m.REGISTRY.counter(
        "dispatch_total", "dispatch decisions by tier chooser").inc(
        dispatcher=dispatcher, choice=choice)
    for fn in list(_subscribers):
        fn(ev)


def subscribe(fn: Callable[[DispatchEvent], None]) -> Callable[[], None]:
    """Register a per-event callback; returns the unsubscriber."""
    _subscribers.append(fn)

    def unsubscribe():
        if fn in _subscribers:
            _subscribers.remove(fn)
    return unsubscribe


def events(dispatcher: Optional[str] = None) -> List[DispatchEvent]:
    """Buffered events, oldest first (optionally one dispatcher's)."""
    if dispatcher is None:
        return list(_events)
    return [e for e in _events if e.dispatcher == dispatcher]


def clear() -> None:
    _events.clear()


def report(evts: Optional[List[DispatchEvent]] = None) -> List[dict]:
    """Aggregate events into {dispatcher, nbits, batch, choice, rule,
    detail, count} rows (insertion-ordered) -- the payload behind
    ``repro.api.dispatch_report()``."""
    rows: dict = {}
    for e in (_events if evts is None else evts):
        key = (e.dispatcher, e.nbits, e.batch, e.choice, e.rule, e.detail)
        rows[key] = rows.get(key, 0) + 1
    return [
        {"dispatcher": d, "nbits": nb, "batch": b, "choice": c,
         "rule": r, "detail": dict(det), "count": n}
        for (d, nb, b, c, r, det), n in rows.items()]


def format_report(rows: Optional[List[dict]] = None) -> List[str]:
    """Human-readable report lines, grouped by dispatcher (shared by
    ``--show-dispatch`` in the examples and the inspect CLI)."""
    rows = report() if rows is None else rows
    lines = []
    for disp in DISPATCHERS:
        mine = [r for r in rows if r["dispatcher"] == disp]
        if not mine:
            continue
        lines.append(f"[{disp}]")
        for r in sorted(mine, key=lambda r: (r["nbits"], r["batch"])):
            extra = "".join(f" {k}={v}" for k, v in r["detail"].items())
            lines.append(
                f"  nbits={r['nbits']} batch={r['batch']}{extra} -> "
                f"{r['choice']!r}  [{r['rule']}]  x{r['count']}")
    return lines
