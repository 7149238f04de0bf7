"""Spans: named, nested wall-time intervals on the profiler's timeline,
and an in-memory buffer of them exportable as Chrome-trace JSON.

``span(name, cat=..., **args)`` wraps its body in
``jax.profiler.TraceAnnotation(name, **args)`` whenever jax is already
imported.  The profiler records the annotation only while a trace is
being taken, so it is always on: a device trace taken around the
program shows every span, nested, on the host timeline beside the
device's ops.  The body may add args as it
learns them (``with span(...) as args: args["compiled"] = True``); they
reach the annotation and the buffer at exit.

Only while observability is on (``repro.api.configure(observability=
True)``) does a span also go into the buffer.  ``cat`` is one of
``CATEGORIES``: "trace" for tracing/compile work, "execute" for
steady-state work; a span whose args say ``compiled=True`` is filed
under "trace" whatever its ``cat``.  The buffer exports to the
``chrome://tracing`` / Perfetto JSON format, where the two categories
sit on separate rows.  Its timestamps are ``time.time_ns``, the
profiler's own clock, in microseconds, so exported spans line up with a
device trace taken at the same time.

Nothing here imports jax (the obs package stays stdlib-only).
"""
from __future__ import annotations

import contextlib
import json
import sys
import time
from typing import List

from repro import config as _config

CATEGORIES = ("trace", "execute")

_spans: List[dict] = []
_MAX_SPANS = 65536                  # hard cap: drop, never grow unbounded


def enabled() -> bool:
    return bool(_config.get_override("observability"))


def _annotation(name: str, args: dict):
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    return jax.profiler.TraceAnnotation(name, **args)


@contextlib.contextmanager
def span(name: str, cat: str = "execute", **args):
    """Annotate the body on the profiler's timeline and, with
    observability on, buffer it.  Yields the args dict: entries the body
    adds are attached at exit."""
    if cat not in CATEGORIES:
        raise ValueError(f"unknown span category {cat!r}; choose from "
                         f"{CATEGORIES}")
    ann = _annotation(name, args)
    given = set(args)
    t0 = time.time_ns()
    try:
        with ann if ann is not None else contextlib.nullcontext():
            try:
                yield args
            finally:
                late = {k: v for k, v in args.items() if k not in given}
                if ann is not None and late:
                    ann.set_metadata(**late)
    finally:
        if enabled() and len(_spans) < _MAX_SPANS:
            _spans.append({
                "name": name,
                "cat": "trace" if args.get("compiled") else cat,
                "ts": t0 / 1e3, "dur": (time.time_ns() - t0) / 1e3,
                "args": dict(args),
            })


def spans() -> List[dict]:
    return list(_spans)


def clear() -> None:
    _spans.clear()


def chrome_trace() -> dict:
    """The span buffer as a Chrome-trace JSON object (complete-event
    "X" phase; load in chrome://tracing or ui.perfetto.dev)."""
    return {
        "displayTimeUnit": "ms",
        "traceEvents": [
            {"name": s["name"], "cat": s["cat"], "ph": "X",
             "ts": s["ts"], "dur": s["dur"], "pid": 1,
             "tid": 1 if s["cat"] == "trace" else 2, "args": s["args"]}
            for s in _spans],
    }


def write_chrome_trace(path: str) -> str:
    with open(path, "w") as f:
        json.dump(chrome_trace(), f, indent=1)
        f.write("\n")
    return path
