"""Reduce a JAX profiler trace of one window to the numbers the per-layer
metrics read: device busy time, device time per Pallas kernel, device
time outside the kernels, the ops that took most time, and the device's
idle time by what the benchmark's host thread was doing.

``load_events`` turns an ``.xplane.pb`` (or the gzipped JSON list that
``save_events`` writes, such as the recorded trace in ``testdata/``)
into plain events; ``reduce`` does the arithmetic on them.

- Device ops are the events of the ``XLA Ops`` line of each
  ``/device:TPU:<i>`` plane; an event's name is its HLO instruction.  An
  op is a Pallas kernel when it is a ``tpu_custom_call``.  The trace does
  not carry the kernel's own name: the instruction is named after the
  jitted wrapper around the ``pallas_call``, so a kernel is known by its
  signature ``<wrapper>/<operand count>``, for example ``_ladder_call/5``
  for ``dot_modmul``'s Montgomery ladder.  Each kernel metric's reader
  names the signature it reads.
- An op's self time is its duration less that of the ops nested in it
  (a ``while`` holds the ops of its body); ``device_ops`` ranks ops by
  self time, grouped by instruction name without its numeric suffix.
- Busy time is the union of the op intervals of a device, averaged over
  the chips used; ``outside_kernels_s`` is the union of the intervals of
  the ops that are not kernels.
- The window's idle gaps are the stretches between the first and last
  ``bench.*`` host annotation in which no op runs; each gap is put under
  the annotation that overlaps it most (``host idle`` where none does).
"""
from __future__ import annotations

import bisect
import collections
import gzip
import json
import pathlib
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_MARK = "bench."
TOP = 10


def op_name(hlo: str) -> str:
    """``%while.409 = (...) while(...)`` -> ``while``."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    base, _, suffix = name.rpartition(".")
    return base if base and suffix.isdigit() else name


def kernel_of(hlo: str) -> str | None:
    """``<wrapper>/<operand count>`` of a Pallas kernel, else None."""
    if 'custom_call_target="tpu_custom_call"' not in hlo:
        return None
    args = hlo.split(" custom-call(", 1)[1].split("), custom_call_target", 1)[0]
    return f"{op_name(hlo)}/{args.count('%')}"


def load_events(path) -> list:
    path = pathlib.Path(path)
    if path.suffix == ".gz":
        with gzip.open(path, "rt") as f:
            return json.load(f)
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    events = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if device:
                    events.append({"plane": plane.name,
                                   "name": op_name(ev.name),
                                   "kernel": kernel_of(ev.name),
                                   "start_ns": ev.start_ns,
                                   "dur_ns": ev.duration_ns})
                elif ev.name.startswith(HOST_MARK):
                    events.append({"plane": "host", "name": ev.name,
                                   "kernel": None, "start_ns": ev.start_ns,
                                   "dur_ns": ev.duration_ns})
    return events


def save_events(events: list, path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def _attribute_gaps(spans: list, host: list, gaps: collections.Counter):
    """Add each idle gap between the first and last host annotation to
    the annotation that overlaps it most (annotations of the one host
    thread do not overlap each other, so ``host`` is sorted by end)."""
    w0, w1 = host[0][0], max(h[1] for h in host)
    ends = [h[1] for h in host]
    edges = [w0] + [x for s in spans for x in s] + [w1]
    for g0, g1 in zip(edges[::2], edges[1::2]):
        g0, g1 = max(g0, w0), min(g1, w1)
        if g1 <= g0:
            continue
        best, label = 0.0, "host idle"
        for h in host[bisect.bisect_right(ends, g0):]:
            if h[0] >= g1:
                break
            ov = _overlap(g0, g1, h[0], h[1])
            if ov > best:
                best, label = ov, h[2]
        gaps[label] += g1 - g0


def _self_times(ops: list):
    """(label, self time) of each op; ops of one line nest or follow."""
    stack = []          # [end, label, child time] of the open ops
    out = []
    for e in sorted(ops, key=lambda e: (e["start_ns"], -e["dur_ns"])):
        start, end = e["start_ns"], e["start_ns"] + e["dur_ns"]
        while stack and stack[-1][0] <= start:
            done = stack.pop()
            out.append((done[1], done[3] - done[2]))
        if stack:
            stack[-1][2] += e["dur_ns"]
        stack.append([end, e["kernel"] or e["name"], 0.0, e["dur_ns"]])
    out.extend((d[1], d[3] - d[2]) for d in stack)
    return out


def reduce(events: list, window_s: float, nchips: int) -> dict:
    ops = [e for e in events if e["plane"] != "host"]
    host = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"], e["name"])
                  for e in events if e["plane"] == "host")
    by_plane = collections.defaultdict(list)
    for e in ops:
        by_plane[e["plane"]].append(e)
    busy, outside = 0.0, 0.0
    kernels = collections.Counter()
    totals = collections.Counter()
    gaps = collections.Counter()
    for plane_ops in by_plane.values():
        spans = _union((e["start_ns"], e["start_ns"] + e["dur_ns"])
                       for e in plane_ops)
        busy += _length(spans)
        outside += _length(_union(
            (e["start_ns"], e["start_ns"] + e["dur_ns"])
            for e in plane_ops if e["kernel"] is None))
        for e in plane_ops:
            if e["kernel"]:
                kernels[e["kernel"]] += e["dur_ns"]
        for label, self_ns in _self_times(plane_ops):
            totals[label] += self_ns
        if host:
            _attribute_gaps(spans, host, gaps)
    scale = 1e-9 / max(1, nchips)
    return {
        "window_s": window_s,
        "busy_s": busy * scale,
        "outside_kernels_s": outside * scale,
        "kernels": {k: v * scale for k, v in kernels.items()},
        "device_ops": [[k, v * scale] for k, v in totals.most_common(TOP)],
        "idle_gaps": [[k, v * scale] for k, v in gaps.most_common(TOP)],
    }


def reduce_dir(trace_dir, window_s: float, nchips: int) -> dict:
    """Reduce the one ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(files)}")
    return reduce(load_events(files[0]), window_s, nchips)
