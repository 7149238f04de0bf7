"""Benchmark harness: one module per paper table/figure.

  bench_add         -> Fig. 3(a)/(b) + Table 1  (add/sub strategies)
  bench_mul         -> Table 4 + Fig. 3(d)      (multiplication routines)
  bench_div         -> beyond-paper             (division subsystem)
  bench_breakdown   -> Tables 1 & 3             (phase-wise attribution)
  bench_gmp         -> Fig. 4                   (GMPbench-style end-to-end)
  bench_crypto      -> Fig. 5 + latency CDFs    (OpenSSL-speed-style)
  bench_exact_accum -> beyond-paper             (exact grad reduction cost)
  bench_roofline    -> EXPERIMENTS.md SSRoofline (TPU terms from the dry-run)

Prints ``name,us_per_call,derived`` CSV.  ``--full`` widens the operand
grid (slower); ``--smoke`` shrinks suites that support it to tiny sizes
and 1-2 reps (the CI bitrot guard).  Individual suites:
``python -m benchmarks.bench_add``.

Perf trajectory across PRs: suites that support it (add, mul, div, and
crypto's modexp section) also produce machine-readable records.
``--json-out DIR`` writes/merges them into DIR/BENCH_<suite>.json
(keyed by op/bits/batch/backend, so smoke and full runs coexist in one
file; the crypto suite's records land in BENCH_modexp.json, see
SUITE_BASELINE); ``--check-baseline`` compares the fresh records
against the committed benchmarks/BENCH_<suite>.json and fails if any
Pallas backend's speedup-vs-jnp regressed by more than
REGRESS_TOLERANCE (the CI perf gate).

The committed smoke-key baselines are conservative FLOORS, not point
estimates: interpret-mode speedup ratios swing 1.5-3x run-to-run on
loaded CPU runners (measured repeatedly across PRs -- e.g. the 512-bit
fused-modexp ratio has been observed anywhere from 0.72x to 1.79x in
back-to-back runs of the same commit), so a floor set near a single
measurement is a coin-flip gate.  Policy: commit floors at ~0.5x of a
representative measured ratio, low enough that only a STRUCTURAL
regression (the fused path no longer decisively beating the jnp
composition) trips them, and rely on the batch-512 rows to record the
measured trajectory at full precision.  To keep regressions diagnosable
from CI logs alone, ``--check-baseline`` prints a ``# perf-gate:`` line
for EVERY gated key showing the fresh measurement, the committed floor,
and the margin between them -- a shrinking margin across PRs is the
early warning; the hard failure only fires below the floor.
"""
import argparse
import inspect
import json
import os
import sys
import time
import traceback

REGRESS_TOLERANCE = 0.20          # fail if speedup drops > 20% vs baseline
BASELINE_DIR = os.path.dirname(os.path.abspath(__file__))

# The crypto suite's machine-readable records are all modexp rows; its
# baseline lives under the op name so the file says what it gates.
SUITE_BASELINE = {"crypto": "modexp"}


def _key(rec):
    return (rec["op"], rec["bits"], rec["batch"], rec["backend"])


def _baseline_path(suite: str, out_dir: str | None = None) -> str:
    name = SUITE_BASELINE.get(suite, suite)
    return os.path.join(out_dir or BASELINE_DIR, f"BENCH_{name}.json")


def write_json(suite: str, records: list, out_dir: str) -> str:
    """Merge records into DIR/BENCH_<suite>.json (new keys win)."""
    os.makedirs(out_dir, exist_ok=True)
    path = _baseline_path(suite, out_dir)
    merged = {}
    if os.path.exists(path):
        with open(path) as f:
            for rec in json.load(f)["records"]:
                merged[_key(rec)] = rec
    for rec in records:
        merged[_key(rec)] = rec
    payload = {
        "schema": ("op,bits,batch,backend,ns_per_op,speedup_vs_jnp"
                   "[,perf_gate{baseline,floor,headroom}]"),
        "records": sorted(merged.values(),
                          key=lambda r: (r["op"], r["bits"], r["batch"],
                                         r["backend"])),
    }
    try:
        # snapshot the arithmetic cache counters alongside the records:
        # a cold operand cache in a CI artifact for a fixed-operand
        # suite is the reuse-regression signal (see api.cache_stats)
        from repro import api
        payload["cache_stats"] = api.cache_stats()
    except Exception:  # noqa: BLE001 - records still land without it
        pass
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    return path


def check_baseline(suite: str, records: list,
                   tolerance: float = REGRESS_TOLERANCE,
                   margins: list[str] | None = None,
                   infos: list[str] | None = None) -> list[str]:
    """Regression messages for Pallas backends vs the committed baseline.

    Compares the machine-independent speedup-vs-jnp ratio (both sides of
    the ratio are measured in the same run, so a slow CI machine cancels
    out); only keys present in both sets are judged.  The gate covers
    the multiply pipeline at kernel-sized operands (op "mul", >= 512
    bits, including the huge-operand "ntt" tier), the division kernel
    (op "div", >= 256 bits: the schoolbook kernel and the fixed-divisor
    "recip_cached" reciprocal path riding the prepared-operand NTT
    cache), the fused windowed modexp ladders (op "modexp", >= 512 bits
    -- the Montgomery fused kernel, the bit-serial composition it must
    keep beating, and the Barrett "barrett_fused" kernel vs its jnp
    composition), and the serving engine's batched-vs-naive throughput
    ratio (op "serve", backend "engine", see bench_serve): smaller
    micro rows and the add strategy sweep are recorded for the
    trajectory but their per-call times are too small for
    run-to-run-stable ratios.

    ``margins``, when given, collects one human-readable line per GATED
    key -- measured ratio, committed floor, and headroom -- so CI logs
    show how close every key sits to its floor even when nothing fails
    (the deflake contract: floors sit at ~0.5x of measured ratios, see
    the module docstring; a margin trending toward 0 is the signal to
    investigate before the hard gate fires).

    ``infos``, when given, collects one line per op-eligible row the
    gate filters SKIP (trajectory-only rows: below min_bits, a
    non-gated backend, or a key with no committed floor) so the CI log
    still shows their measured ratios -- headroom you can read without
    promoting the row to a hard gate.
    """
    path = _baseline_path(suite)
    if not os.path.exists(path):
        return []
    with open(path) as f:
        baseline = {_key(r): r for r in json.load(f)["records"]}
    problems = []
    min_bits = {"mul": 512, "div": 256, "modexp": 512, "serve": 256}

    def gated(rec) -> bool:
        if rec["op"] not in min_bits or rec["bits"] < min_bits[rec["op"]]:
            return False
        if rec["op"] == "div":
            # schoolbook kernel + the fixed-divisor cached-reciprocal path
            return rec["backend"] in ("schoolbook", "recip_cached")
        if rec["op"] == "serve":
            # gate the headline engine-vs-cold-naive throughput ratio;
            # engine_vs_warm and naive rows are trajectory-only
            return rec["backend"] == "engine"
        return ("pallas" in rec["backend"] or "kernel" in rec["backend"]
                or rec["backend"] in ("ntt", "barrett_fused"))

    for rec in records:
        if not rec.get("speedup_vs_jnp"):
            continue
        base = baseline.get(_key(rec))
        if not gated(rec) or not base or not base.get("speedup_vs_jnp"):
            if infos is not None and rec["op"] in min_bits \
                    and rec["speedup_vs_jnp"] != 1.0:
                committed = (f"committed {base['speedup_vs_jnp']:.2f}x"
                             if base and base.get("speedup_vs_jnp")
                             else "no committed floor")
                infos.append(
                    f"{suite}:{'/'.join(map(str, _key(rec)))} measured "
                    f"{rec['speedup_vs_jnp']:.2f}x ({committed}; "
                    f"trajectory row, ungated)")
            continue
        floor = base["speedup_vs_jnp"] * (1.0 - tolerance)
        # annotate the record itself so --json-out artifacts carry the
        # gate verdict (floor + headroom) next to the measurement
        rec["perf_gate"] = {
            "baseline": base["speedup_vs_jnp"], "floor": round(floor, 4),
            "headroom": round(rec["speedup_vs_jnp"] / floor - 1.0, 4),
        }
        if margins is not None:
            margins.append(
                f"{suite}:{'/'.join(map(str, _key(rec)))} measured "
                f"{rec['speedup_vs_jnp']:.2f}x vs floor {floor:.2f}x "
                f"(headroom {rec['speedup_vs_jnp'] / floor - 1.0:+.0%})")
        if rec["speedup_vs_jnp"] < floor:
            problems.append(
                f"{suite}:{'/'.join(map(str, _key(rec)))} speedup "
                f"{rec['speedup_vs_jnp']:.2f}x < {floor:.2f}x "
                f"(baseline {base['speedup_vs_jnp']:.2f}x - {tolerance:.0%})")
    return problems


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names (e.g. add,mul)")
    ap.add_argument("--json-out", default=None, metavar="DIR",
                    help="write/merge BENCH_<suite>.json records here")
    ap.add_argument("--check-baseline", action="store_true",
                    help="fail if a Pallas backend regressed >20%% vs the "
                         "committed BENCH_<suite>.json speedup baseline")
    args = ap.parse_args()

    from repro.kernels.common.runtime import use_compile_cache
    use_compile_cache()
    from benchmarks import (bench_add, bench_breakdown, bench_crypto,
                            bench_div, bench_exact_accum, bench_gmp,
                            bench_mul, bench_roofline, bench_serve)
    suites = {
        "add": bench_add, "mul": bench_mul, "div": bench_div,
        "breakdown": bench_breakdown, "gmp": bench_gmp,
        "crypto": bench_crypto, "exact_accum": bench_exact_accum,
        "roofline": bench_roofline, "serve": bench_serve,
    }
    pick = args.only.split(",") if args.only else list(suites)
    print("name,us_per_call,derived")
    failures = 0
    regressions: list[str] = []
    for name in pick:
        mod = suites[name]
        t0 = time.time()
        sig = inspect.signature(mod.run).parameters
        kwargs = {"full": args.full}
        if args.smoke and "smoke" in sig:
            kwargs["smoke"] = True
        records: list = []
        if "records" in sig:
            kwargs["records"] = records
        try:
            for line in mod.run(**kwargs):
                print(line, flush=True)
            print(f"# suite {name}: {time.time() - t0:.1f}s", flush=True)
        except Exception:  # noqa: BLE001 - report and continue
            failures += 1
            print(f"# suite {name} FAILED:", flush=True)
            traceback.print_exc()
            continue
        # check BEFORE writing: --json-out pointed at the baseline dir
        # must not overwrite the baseline the check compares against.
        # --json-out alone still runs the comparison (problems
        # discarded) so the written records carry perf_gate headroom.
        if records and (args.check_baseline or args.json_out):
            margins: list[str] = []
            infos: list[str] = []
            problems = check_baseline(name, records,
                                      margins=margins, infos=infos)
            if args.check_baseline:
                regressions.extend(problems)
                for line in margins:
                    print(f"# perf-gate: {line}", flush=True)
                for line in infos:
                    print(f"# info: {line}", flush=True)
        if records and args.json_out:
            path = write_json(name, records, args.json_out)
            print(f"# wrote {path} ({len(records)} records)", flush=True)
    from repro.kernels.common import autotune
    if autotune.enabled() and autotune.cache_summary():
        print(f"# autotuned tiles: {autotune.cache_summary()}", flush=True)
    for msg in regressions:
        print(f"# PERF REGRESSION: {msg}", flush=True)
    if failures or regressions:
        sys.exit(1)


if __name__ == "__main__":
    main()
