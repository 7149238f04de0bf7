"""Find a serving cell's knee: the highest offered rate the engine keeps up
with.

    python3 chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> [--rates r ...]

One set-up, then one window per rate, in this one process.  Without
``--rates`` the rates are fractions of the capacity that back-to-back
full batches give (``FRACTIONS``).  A rate is kept up with when the
last request's answer comes within the deadline flush plus
``DRAIN_BATCHES`` full batches of its scheduled send: a backlog that
grows through the window leaves a drain that grows with it.  (The
engine sheds nothing in this loop, see ``drivers/serve_open.py``, so the
drain decides.)  The knee is the highest such rate below the first that
is not.  The result's last line is JSON: every rate's numbers, the knee
and the cell rate, ``CELL_SHARE`` x the knee, which is written into the
cell's workload file by hand.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench import reference, run  # noqa: E402

FRACTIONS = (0.5, 0.6, 0.7, 0.8, 0.85, 0.9, 0.95, 1.0, 1.1)
DRAIN_BATCHES = 3
CELL_SHARE = 0.8
CAPACITY_BATCHES = 10


def capacity(state) -> float:
    """Requests per second that back-to-back full batches serve."""
    eng, slots = state.engine, state.engine.cfg.slots
    values = state.values(CAPACITY_BATCHES * slots, salt="capacity")
    limbs = reference.to_limbs(values, state.nlimbs)
    t = time.perf_counter()
    for i in range(len(values)):
        eng.submit(state.request(-1 - i, limbs[i]), now=time.perf_counter())
    return len(values) / (time.perf_counter() - t)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="*")
    args = ap.parse_args(argv)
    spec, _ = run.chip_setup(args.workload)
    if spec["driver"] != "serve_open":
        sys.exit(f"sweep: {args.workload} is not a serving cell")
    driver = run.load(run.BENCH / "drivers" / "serve_open.py")
    state = driver.setup(spec["config"], spec["traffic"], args.seed)
    cap = capacity(state)
    drain_limit = (state.engine.cfg.max_wait_s
                   + DRAIN_BATCHES * state.engine.cfg.slots / cap)
    rates = args.rates or [round(f * cap, 1) for f in FRACTIONS]
    rows, knee = [], None
    for rate in rates:
        rec = driver.window(state, args.seconds, rate=rate)
        lat = np.asarray(rec["latency_s"]) * 1e3
        row = {"rate_per_s": rate, "attempted": rec["attempted"],
               "shed": rec["shed"], "drain_s": rec["drain_s"],
               "completed_per_s": rec["completed_in_window"] / args.seconds,
               "p50_ms": float(np.percentile(lat, 50)),
               "p99_ms": float(np.percentile(lat, 99)),
               "batch_fill": 100.0 * rec["engine"]["served"]
               / max(1, rec["engine"]["batches"] * rec["slots"])}
        row["keeps_up"] = row["drain_s"] <= drain_limit
        rows.append(row)
        print(json.dumps(row), flush=True)
        if not row["keeps_up"]:
            break
        knee = rate
    state.close()
    print(json.dumps({"workload": args.workload, "capacity_per_s": cap,
                      "drain_limit_s": drain_limit,
                      "rows": rows, "knee_per_s": knee,
                      "cell_rate_per_s": None if knee is None
                      else round(CELL_SHARE * knee, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
