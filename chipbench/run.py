"""Run one benchmark cell once on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 -m chipbench.run ...            (the same)

The cell is found by name: ``BENCHMARK.json`` lists it and its metrics,
``chipbench/workloads/<cell>.json`` names its configuration, driver and
traffic, ``chipbench/configs/<config>.json`` holds the deployment,
``chipbench/drivers/<driver>.py`` generates the traffic, and
``chipbench/metrics/<metric>.py`` reads each metric from the run's
record.  A new cell or metric is new files plus entries in
``BENCHMARK.json``; no file here changes.

Set-up (``setup_s``) runs from the start of this process to the start of
the window: loading JAX and the chip, keys and operands from the seed,
and the cell's own programs, compiled or loaded from JAX's persistent
cache in ``<checkout>/.jax_cache``.  The window runs for ``--seconds``.
Then the peak device memory is read, the program's state is freed, and
the answers of the window are compared with the plain reference
(``reference.py``).  With ``--trace 1`` the window runs under the JAX
profiler and the per-layer metrics are reported instead of the
end-to-end ones.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``, each number compared beside its limit (also the last
lines of stderr).  Without a TPU, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class BenchError(RuntimeError):
    """The run cannot give a result (not a wrong answer: a broken run)."""


def load(path: pathlib.Path):
    """Import one harness file by path (metric names hold dots)."""
    if not path.is_file():
        raise BenchError(f"missing harness file {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: pathlib.Path):
    if not path.is_file():
        raise BenchError(f"missing harness file {path}")
    return json.loads(path.read_text())


def cell_spec(name: str, root: pathlib.Path = ROOT) -> dict:
    """Everything the files say about one cell, found by its name."""
    bench = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json; "
                         f"choose from {sorted(cells)}")
    entry = cells[name]
    wl = read_json(root / "chipbench" / "workloads" / f"{name}.json")
    if wl["config"] != entry["config"]:
        raise BenchError(f"{name}: the workload file names config "
                         f"{wl['config']!r}, BENCHMARK.json {entry['config']!r}")
    conf = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    end_to_end = [m for m in bench["end_to_end"]
                  if name in m.get("workloads", [name])]
    reported = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"]
                 if name in m["workloads"] or ("workloads" not in m and
                                               m["moves"] in reported)]
    return {"name": name, "chips": entry["chips"], "driver": wl["driver"],
            "traffic": wl["traffic"], "trace_seconds": wl["trace_seconds"],
            "config": read_json(root / conf["file"]),
            "end_to_end": end_to_end, "per_layer": per_layer}


def require_tpu(chips: int):
    """The chip, or exit: there is no CPU or interpret-mode fallback."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as exc:
        sys.exit(f"chipbench: no accelerator: {exc}")
    if devs[0].platform != "tpu":
        sys.exit(f"chipbench: JAX's first device is {devs[0].platform!r}, "
                 f"not a TPU; this benchmark has no CPU fallback")
    if len(devs) < chips:
        sys.exit(f"chipbench: the cell needs {chips} chips, JAX sees "
                 f"{len(devs)}")
    return devs


def device_info(devs, root: pathlib.Path) -> dict:
    peaks = read_json(root / "chipbench" / "peaks.json")
    kind = devs[0].device_kind
    if devs[0].platform == "tpu" and kind not in peaks["devices"]:
        raise BenchError(f"device kind {kind!r} is not in peaks.json")
    stats = [d.memory_stats() or {} for d in devs]
    return {"platform": devs[0].platform, "kind": kind, "count": len(devs),
            "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0)
                                     for s in stats)}


class Tracer:
    """The JAX profiler over the last ``traced_s`` seconds of the window
    and the drain after it: the driver calls ``poll`` as it goes, the
    harness ``stop`` once the window's answers are in."""

    def __init__(self, trace_dir: str, window_s: float, traced_s: float):
        import jax

        self.dir, self.from_s = trace_dir, max(0.0, window_s - traced_s)
        self.t_start = self.t_stop = None
        self.options = jax.profiler.ProfileOptions()
        self.options.python_tracer_level = 0     # host TraceMe spans only
        self.options.enable_hlo_proto = False

    def poll(self, t0: float, now: float) -> None:
        if self.t_start is None and now >= t0 + self.from_s:
            self._start()

    def _start(self):
        import jax

        jax.profiler.start_trace(self.dir, profiler_options=self.options)
        self.t_start = time.perf_counter()

    def stop(self) -> float:
        import jax

        if self.t_start is None:
            self._start()
        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        return self.t_stop - self.t_start


class CompileCounter:
    """Counts JAX traces, lowerings and compiles while ``armed``."""

    def __init__(self):
        import jax

        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **kw):
        if self.armed and event in COMPILE_EVENTS:
            self.count += 1


def dispatch_choices(rows: list) -> dict:
    """The tiers the program's dispatchers chose in set-up, by
    dispatcher (``repro.api.dispatch_report`` rows)."""
    out: dict = {}
    for r in rows:
        out.setdefault(r["dispatcher"], set()).add(r["choice"])
    return {k: sorted(v) for k, v in out.items()}


def metric_values(metrics: list, record: dict, root: pathlib.Path) -> dict:
    out = {}
    for m in metrics:
        reader = load(root / "chipbench" / "metrics" / f"{m['name']}.py")
        value = reader.read(record)
        if value is None:
            raise BenchError(f"metric {m['name']}: nothing to read in this "
                             f"cell")
        if not math.isfinite(value):
            raise BenchError(f"metric {m['name']} reads {value}")
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             devs=None, root: pathlib.Path = ROOT, wrap=None) -> dict:
    """One run of one cell: set-up, window, memory, reference check.

    ``devs`` are the devices the run reports (``jax.devices()`` when
    None); ``wrap`` wraps the timed path (controls and fault tests
    put a broken path in the program's place through it)."""
    import jax

    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    spec = cell_spec(name, root)
    driver = load(root / "chipbench" / "drivers" / f"{spec['driver']}.py")
    devs = devs or jax.devices()
    counter = CompileCounter()
    from repro import api
    with api.configure(observability=True):   # dispatch records its tiers
        state = driver.setup(spec["config"], spec["traffic"], seed, wrap=wrap)
    dispatch = dispatch_choices(api.dispatch_report())
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        tracer = (Tracer(tdir, seconds, spec["trace_seconds"]) if trace
                  else None)
        counter.armed = True
        record = driver.window(state, seconds, tracer=tracer)
        counter.armed = False
        if trace:
            window_s = tracer.stop()
            reduce = load(root / "chipbench" / "trace_reduce.py")
            record["trace"] = reduce.reduce_dir(
                tdir, window_s, len(devs[:spec["chips"]]))
            done = record["done_at"]
            record["trace"]["ops"] = int(sum(
                n for t, n in done if tracer.t_start <= t <= tracer.t_stop))
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)
    record["setup_s"] = record["t0"] - T_START
    record["dispatch"] = dispatch
    if counter.count:
        raise BenchError(f"{counter.count} JAX traces or compiles inside "
                         f"the measured window")
    device = device_info(devs, root)
    state.close()
    checks = driver.check(state, record)
    del state
    metrics = spec["per_layer"] if trace else spec["end_to_end"]
    result = {
        "correct": all(v <= limit for v, limit in checks.values()),
        "attempted": int(record["attempted"]),
        "failed": int(record.get("shed", 0) + record.get("lost", 0)),
        "metrics": metric_values(metrics, record, root),
        "device": device,
    }
    if trace:
        tr = record["trace"]
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": limit}
                        for k, (v, limit) in checks.items()}
    return result


def chip_setup(cell: str):
    """An entry point's start: the cell's spec and its chips, the program
    on the path, and JAX's persistent cache in the checkout whatever the
    environment says (the path is part of the cache's key, and two
    checkouts must share nothing)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    spec = cell_spec(cell)
    devs = require_tpu(spec["chips"])
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from repro.kernels.common.runtime import use_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    use_compile_cache()
    return spec, devs[:spec["chips"]]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec, devs = chip_setup(args.workload)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), devs=devs)
    for k, c in result["checks"].items():
        print(f"chipbench check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchError as exc:
        sys.exit(f"chipbench: {exc}")
