"""Chaos harness: deterministic fault injection against the serving
engine, with EXACT counter accounting as the pass/fail gates.

Installs ``repro.resilience.inject`` specs (compile failures on the
fused modexp ladder's guarded dispatch, flush-time errors, latency
spikes, result-limb corruption), warms a mixed mod_exp + RSA engine,
replays a Poisson trace, and then asserts the fault-tolerance contract:

  1. zero unhandled exceptions -- every injected failure was absorbed
     by guard fallback, flush retry, or bucket degradation;
  2. every served (non-shed) result is bit-exact against the python-int
     reference -- corrupted lanes were caught by the residue/witness
     self-check and repaired;
  3. zero retrace ALARMS -- ``on_retrace="raise"`` is armed, so the
     run itself proves no unexpected recompiles (degradation-forced
     recompiles are declared via the engine's expected-trace flag);
  4. ``fallback_total{reason="injected"}`` equals the number of
     realized compile_fail injections, one-to-one;
  5. ``selfcheck_failures_total`` equals the number of realized
     corrupt injections (each flips one bit of one real lane);
  6. every requested fault kind actually fired (non-vacuity).

Usage (CI smoke):
  PYTHONPATH=src python -m repro.launch.chaos_bignum --seed 0 \
      --inject compile_fail,latency,corrupt --smoke \
      --metrics-out chaos_metrics.json
"""
from __future__ import annotations

import argparse
import json
import sys
import warnings

import numpy as np

from repro import api
from repro.configs.dot_bignum import ServeConfig
from repro.launch.serve_bignum import build_ops
from repro.obs import metrics as _metrics
from repro.resilience import inject, selfcheck
from repro.resilience.breaker import BREAKER
from repro.resilience.guard import METRIC as FALLBACK
from repro.serve.bignum_engine import (
    BignumEngine, poisson_trace, replay_trace)


def install_specs(kinds, seed: int) -> None:
    """The injection plan.  Sites are chosen so every resilience layer
    absorbs at least one fault: ``compile_fail`` hits the guarded
    kernel dispatch at TRACE time (the fused modexp ladder tiers, so
    warm() sees it and the guard falls through pallas -> jnp ->
    reference inside the jit); ``flush_error`` hammers one bucket's
    flush until retries exhaust and the engine degrades it a backend
    tier; ``latency`` stalls flushes; ``corrupt`` flips result bits
    downstream of a correct kernel for the self-check to catch."""
    if "compile_fail" in kinds:
        inject.install("compile_fail", "modexp/", every=1, count=2)
    if "flush_error" in kinds:
        inject.install("flush_error", "serve/flush/rsa_verify",
                       every=1, count=3)
    if "latency" in kinds:
        inject.install("latency", "serve/flush", every=3, count=3,
                       delay_s=0.02)
    if "corrupt" in kinds:
        inject.install("corrupt", "serve/flush", every=5, seed=seed)


def run(args) -> int:
    kinds = [k for k in args.inject.split(",") if k]
    bad = set(kinds) - set(inject.KINDS)
    if bad:
        raise SystemExit(f"unknown inject kinds {sorted(bad)}; "
                         f"choose from {inject.KINDS}")
    n_requests = 40 if args.smoke else args.requests

    # degradation is what the harness exercises: opt in to the kernel
    # fallback, which is off by default
    api.configure(observability=True, selfcheck="warn",
                  on_retrace="raise", kernel_fallback=True)
    _metrics.REGISTRY.reset()
    BREAKER.reset()
    inject.clear()
    install_specs(kinds, args.seed)

    templates, warm = build_ops("mixed", args.bits, args.groups,
                                args.seed)
    trace = poisson_trace(templates, n_requests, args.rate,
                          seed=args.seed)
    engine = BignumEngine(ServeConfig(), backend=None)
    failures = []
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", selfcheck.SelfCheckWarning)
            for w in warm:
                engine.warm(**w)
            res = replay_trace(engine, trace)
            engine.close()
    finally:
        plan = inject.log()
        inject.clear()
        BREAKER.reset()

    # gate 2: bit-exactness of every served result vs the host reference
    wrong = shed = 0
    for r in trace:
        if r.shed:
            shed += 1
            continue
        v = api.from_limbs(np.asarray(r.value, np.uint32).reshape(-1))
        expect = selfcheck.repair_lane(r.op, v, modulus=r.modulus,
                                       exponent=r.exponent, key=r.key)
        if api.from_limbs(np.asarray(r.result)) != expect:
            wrong += 1
    if wrong:
        failures.append(f"{wrong} served result(s) not bit-exact")

    # gates 3-5: counters vs the realized injection plan, exactly
    reg = _metrics.REGISTRY
    retraces = reg.counter("retraces_total").total()
    if retraces:
        failures.append(f"{int(retraces)} unexpected retrace(s)")
    injected = reg.counter(FALLBACK).total(reason="injected")
    n_compile = sum(1 for e in plan if e["kind"] == "compile_fail")
    if injected != n_compile:
        failures.append(
            f"fallback_total{{reason=injected}} = {int(injected)} but "
            f"{n_compile} compile_fail injection(s) realized")
    sc = reg.counter(selfcheck.METRIC).total()
    n_corrupt = sum(1 for e in plan if e["kind"] == "corrupt")
    if sc != n_corrupt:
        failures.append(
            f"selfcheck_failures_total = {int(sc)} but {n_corrupt} "
            f"corrupt injection(s) realized")

    # gate 6: every requested kind fired at least once
    realized = {e["kind"] for e in plan}
    for k in kinds:
        if k not in realized:
            failures.append(f"requested fault kind {k!r} never fired")

    st = engine.stats
    by_kind = ", ".join(
        "{}={}".format(k, sum(1 for e in plan if e["kind"] == k))
        for k in sorted(realized)) or "none"
    print(f"[chaos_bignum] {res.n} reqs ({shed} shed) in "
          f"{res.makespan_s:.3f}s | {len(plan)} injections realized "
          f"({by_kind})")
    print(f"[chaos_bignum] retries={st.retries} degraded={st.degraded} "
          f"selfcheck_failures={st.selfcheck_failures} "
          f"deadline_misses={st.deadline_misses} "
          f"fallback_injected={int(injected)} retrace_alarms="
          f"{int(retraces)}")

    if args.metrics_out:
        snap = api.metrics() or _metrics.REGISTRY.snapshot()
        payload = {"gates_failed": failures, "injections": plan,
                   "shed": shed, "metrics": snap}
        with open(args.metrics_out, "w") as f:
            json.dump(payload, f, indent=2, default=str)
        print(f"[chaos_bignum] metrics -> {args.metrics_out}")

    if failures:
        for f in failures:
            print(f"[chaos_bignum] GATE FAILED: {f}", file=sys.stderr)
        return 1
    print("[chaos_bignum] all gates passed")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--inject", default=",".join(inject.KINDS),
                    help="comma list of fault kinds to install")
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--smoke", action="store_true",
                    help="40-request CI-sized run")
    ap.add_argument("--bits", type=int, default=256)
    ap.add_argument("--groups", type=int, default=3)
    ap.add_argument("--rate", type=float, default=200.0)
    ap.add_argument("--metrics-out", default=None)
    args = ap.parse_args(argv)
    return run(args)


if __name__ == "__main__":
    sys.exit(main())
