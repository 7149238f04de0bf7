"""The harness on the CPU, with no chip: file layout, trace reduction,
both drivers end to end at a tiny size, and the refusal to run a cell
anywhere but on a TPU.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest -q chipbench/tests
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import pytest

from conftest import REPO, SECONDS, SEED, TINY_CELLS

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CB = REPO / "chipbench"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def test_benchmark_json_names_and_bounds():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for entry in BENCH["configs"] + BENCH["workloads"] + metrics:
        assert NAME.match(entry["name"]), entry["name"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert "\n" not in m["layer"] and m["layer"]
    for w in BENCH["workloads"]:
        assert w["chips"] == 1 and len(w["why"]) <= 200


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_workload_files_name_what_exists(cell):
    from chipbench import run

    spec = run.cell_spec(cell)
    assert (CB / "drivers" / f"{spec['driver']}.py").is_file()
    conf = {c["name"]: c for c in BENCH["configs"]}[
        json.loads((CB / "workloads" / f"{cell}.json").read_text())["config"]]
    assert (REPO / conf["file"]).is_file()
    assert spec["config"]["reduced"] == conf["reduced"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert (CB / "metrics" / f"{m['name']}.py").is_file(), m["name"]
    reported = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2
    assert spec["per_layer"]
    if spec["driver"] == "serve_open":
        assert isinstance(spec["traffic"]["rate_per_s"], float)


def test_every_metric_file_is_listed():
    listed = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    files = {p.name[:-3] for p in (CB / "metrics").glob("*.py")}
    assert files == listed


def test_trace_reduction_on_recorded_trace():
    from chipbench import trace_reduce

    events = trace_reduce.load_events(CB / "testdata" / "trace_small.json.gz")
    got = trace_reduce.reduce(events, window_s=1.0, nchips=1)
    want = json.loads((CB / "testdata" / "trace_small.expect.json")
                      .read_text())
    assert got["kernels"] == pytest.approx(want["kernels"], rel=1e-12)
    for key in ("busy_s", "outside_kernels_s"):
        assert got[key] == pytest.approx(want[key], rel=1e-12)
    for key in ("device_ops", "idle_gaps"):
        assert [n for n, _ in got[key]] == [n for n, _ in want[key]]
        assert [v for _, v in got[key]] == pytest.approx(
            [v for _, v in want[key]], rel=1e-12)
    # two Montgomery ladders of 136.02 ms each, read by hand off the trace
    assert got["kernels"]["_ladder_call/5"] == pytest.approx(0.27203986)
    idle = 1 - got["busy_s"] / got["window_s"]
    assert idle == pytest.approx(1 - 0.315028473 / 1.0)


KERNEL_METRICS = {"kara_us_per_op": ("_call/2", "mul", "pallas_kara"),
                  "ladder_us_per_op": ("_ladder_call/5", "modexp", "pallas"),
                  "ntt_us_per_op": ("_call/4", "mul", "ntt")}


def _kernel_record(kernels: dict, dispatch: dict) -> dict:
    return {"kind": "arith", "ops_done": 4096, "dispatch": dispatch,
            "trace": {"kernels": kernels, "busy_s": 0.5, "window_s": 1.0,
                      "outside_kernels_s": 0.5, "ops": 4096}}


@pytest.mark.parametrize("name", sorted(KERNEL_METRICS))
def test_kernel_metric_without_events_fails(name):
    from chipbench import run

    kernel, dispatcher, choice = KERNEL_METRICS[name]
    metric = {"name": name, "unit": "us"}
    record = _kernel_record({}, {dispatcher: [choice]})
    with pytest.raises(run.BenchError, match="nothing to read"):
        run.metric_values([metric], record, REPO)
    record = _kernel_record({kernel: 0.4096}, {dispatcher: [choice]})
    got = run.metric_values([metric], record, REPO)[name]["value"]
    assert got == pytest.approx(100.0)


@pytest.mark.parametrize("name", sorted(KERNEL_METRICS))
def test_kernel_metric_of_another_tier_fails(name):
    """Events under the kernel's name, but the dispatcher chose another
    tier (or another besides): the events are not this kernel's."""
    from chipbench import run

    kernel, dispatcher, choice = KERNEL_METRICS[name]
    metric = {"name": name, "unit": "us"}
    for dispatch in ({}, {dispatcher: ["jnp"]},
                     {dispatcher: sorted([choice, "zzz_other"])}):
        record = _kernel_record({kernel: 0.4096}, dispatch)
        with pytest.raises(run.BenchError, match="nothing to read"):
            run.metric_values([metric], record, REPO)


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_tiny_cell_runs_and_is_correct(tiny_root, cell):
    from chipbench import run

    res = run.run_cell(cell, SEED, SECONDS, False, root=tiny_root)
    assert RESULT_KEYS <= set(res) and list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    spec = run.cell_spec(cell, tiny_root)
    assert set(res["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    assert res["device"]["platform"] == "cpu"
    json.dumps(res)


def test_new_cell_is_new_files_only(tiny_root):
    """Adding the tiny cells changed no file the benchmark already had."""
    for path in CB.rglob("*"):
        rel = path.relative_to(REPO)
        if path.is_file() and "__pycache__" not in rel.parts \
                and rel.parts[1] != "tests":
            assert (tiny_root / rel).read_bytes() == path.read_bytes(), rel


@pytest.mark.parametrize("entry", ["chipbench/run.py", "-m chipbench.run"])
def test_run_refuses_a_cpu(entry, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", HOME=str(tmp_path))
    cmd = [sys.executable, *entry.split(), "--workload",
           BENCH["workloads"][0]["name"], "--seed", str(SEED),
           "--seconds", "1", "--trace", "0"]
    proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "not a TPU" in proc.stderr
