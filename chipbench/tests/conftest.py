"""Tiny cells for the harness tests: a copy of the benchmark's tree with
new configuration and workload files and new ``BENCHMARK.json`` entries
only, run on the CPU with the Pallas kernels in interpret mode."""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CONFIGS = {
    "rsa256-test": ("rsa2048-tls13", {"key_bits": 256}),
    "mul-grid-test": ("dot-mul-grid", {"batch": 16}),
}
TINY_CELLS = {
    "rsa256-sign-test": ("rsa256-test", "serve_open",
                         {"op": "rsa_sign", "rate_per_s": 40.0}),
    "rsa256-verify-test": ("rsa256-test", "serve_open",
                           {"op": "rsa_verify", "rate_per_s": 40.0}),
    "mul512-test": ("mul-grid-test", "arith_closed",
                    {"bits": 512, "batch": 16, "operand_sets": 2}),
}
SECONDS = 1.0
SEED = 2 ** 33 + 17          # larger than 32 signed bits hold


def make_tiny_root(dst: pathlib.Path) -> pathlib.Path:
    """Add the tiny cells as new files and new entries, nothing else."""
    shutil.copytree(REPO / "chipbench", dst / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cb = dst / "chipbench"
    for name, (base, changes) in TINY_CONFIGS.items():
        conf = json.loads((cb / "configs" / f"{base}.json").read_text())
        conf.update(changes, name=name, reduced=sorted(changes))
        (cb / "configs" / f"{name}.json").write_text(json.dumps(conf))
        bench["configs"].append({
            "name": name, "source": conf["source"],
            "file": f"chipbench/configs/{name}.json",
            "reduced": sorted(changes), "why": "tiny CPU test size"})
    for name, (config, driver, traffic) in TINY_CELLS.items():
        (cb / "workloads" / f"{name}.json").write_text(json.dumps(
            {"config": config, "driver": driver, "traffic": traffic,
             "trace_seconds": SECONDS}))
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": name, "chips": 1,
                                   "why": "tiny CPU test size"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            listed = m.get("workloads", [])
            if any(_driver_of(w) == driver for w in listed):
                listed.append(name)
    (dst / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return dst


def _driver_of(cell: str) -> str:
    if cell in TINY_CELLS:
        return TINY_CELLS[cell][1]
    wl = json.loads((REPO / "chipbench" / "workloads" / f"{cell}.json")
                    .read_text())
    return wl["driver"]


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    if os.environ.get("JAX_PLATFORMS", "cpu") != "cpu":
        pytest.skip("the tiny cells run on the CPU")
    return make_tiny_root(tmp_path_factory.mktemp("bench"))
