"""Chip smoke run: the bignum main path once on one TPU, every lane
checked against python ints.

    python chip_smoke.py [--seed N]

Phases, all in this one process (a chip belongs to one process):

  serve  RSA-2048 through ``BignumEngine`` with the default
         ``ServeConfig``: two keys made from ``--seed``; CRT decrypt,
         sign, verify (e = 65537) and 2048-bit ``mod_exp`` with a
         2048-bit exponent, each warmed first and then fed REQUESTS
         requests so every batch fills.
  arith  ``api.mul`` at 4096 bits x 4096 lanes and 16384 bits x 64
         lanes; ``api.divmod`` at 512/256 and 4096/2048 bits x 256 lanes.

Everything runs under ``configure(kernel_fallback=False,
observability=True)``: a kernel that fails to lower is an error, and
the dispatch trace names the tier each phase ran on.  One line per
phase reports the tier, the fallback_total series, and smoke timings
(compile and steady seconds of one run; not metrics).  The run fails
(exit 1) if no TPU is found, if any lane differs from python ints, if
any fallback_total series exists, if the engine degraded a bucket or
retraced after warm, or if a phase did not run on its kernel tier.  The
last line of stdout is the JSON verdict with the device JAX reports.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import numpy as np

# The tier each phase must reach.  RSA verify (e = 65537) is the one
# control: a 17-bit exponent takes the jnp ladder by design
# (MODEXP_DISPATCH.fused_min_exp_bits).
SERVE_TIERS = {"rsa_decrypt": {"pallas"}, "rsa_sign": {"pallas"},
               "rsa_verify": {"jnp"}, "mod_exp": {"pallas"}}
KERNEL_MUL_TIERS = {"pallas", "pallas_kara", "pallas_mxu", "ntt"}
RSA_BITS = 2048
REQUESTS = 64          # per serving op: a multiple of the 8 slots
# (operand bits, batch, tier); the first is DoTBenchConfig's batch
MUL_CASES = ((4096, 4096, {"pallas_kara"}), (16384, 64, {"ntt"}))
# (dividend bits, divisor bits, batch, tier)
DIV_CASES = ((512, 256, 256, {"schoolbook"}), (4096, 2048, 256, {"recip"}))


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def require_tpu():
    try:
        dev = jax.devices()[0]
    except RuntimeError as exc:
        fail(f"no TPU found: {exc}")
    if dev.platform != "tpu":
        fail(f"no TPU found: JAX's first device is {dev.platform!r}; this "
             f"run has no CPU or interpret-mode fallback")
    return dev


def import_repro():
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    try:
        from repro import api
        from repro.kernels.common.runtime import use_compile_cache
    except ImportError as exc:
        fail(f"the repro package is not beside this script: {exc}")
    return api, use_compile_cache


def to_ints(limbs) -> list:
    """(N, m) uint32 limbs -> python ints (the reference's own converter,
    independent of the code under test)."""
    a = np.ascontiguousarray(np.asarray(limbs, np.uint32).astype("<u4"))
    return [int.from_bytes(row.tobytes(), "little") for row in a]


def random_limbs(rng, batch: int, bits: int) -> np.ndarray:
    return rng.integers(0, 1 << 32, (batch, bits // 32), dtype=np.uint64
                        ).astype(np.uint32)


class Report:
    def __init__(self, api):
        self.api = api
        self.errors: list = []

    def tiers(self, dispatcher: str) -> set:
        return {r["choice"] for r in self.api.dispatch_report()
                if r["dispatcher"] == dispatcher}

    def fallbacks(self) -> dict:
        return self.api.metrics()["counters"].get("fallback_total", {})

    def phase(self, name: str, ok_lanes: bool, tiers: set, want: set,
              **extra) -> None:
        fb = self.fallbacks()
        line = {"phase": name, "tier": sorted(tiers),
                "lanes_exact": ok_lanes, "fallback_total": fb, **extra}
        print(json.dumps(line), flush=True)
        if not ok_lanes:
            self.errors.append(f"{name}: lanes differ from python ints")
        if fb:
            self.errors.append(f"{name}: fallback_total {fb}")
        if not tiers & want:
            self.errors.append(f"{name}: tier {sorted(tiers)}, expected "
                               f"one of {sorted(want)}")


def serve_phase(api, rep: Report, seed: int) -> None:
    from repro.obs import trace as otrace
    from repro.serve.bignum_engine import BignumEngine, BignumRequest

    t0 = time.perf_counter()
    keys = [api.generate_key(RSA_BITS, seed=seed + i) for i in range(2)]
    print(json.dumps({"phase": "keygen", "keys": len(keys), "bits": RSA_BITS,
                      "smoke_seconds": round(time.perf_counter() - t0, 3)}),
          flush=True)
    rng = np.random.default_rng(seed)
    nbytes = RSA_BITS // 8
    exps = [int.from_bytes(rng.bytes(nbytes), "little") | (1 << RSA_BITS - 1)
            for _ in keys]
    eng = BignumEngine()
    ops = ("rsa_decrypt", "rsa_sign", "rsa_verify", "mod_exp")
    for op in ops:
        otrace.clear()
        t0 = time.perf_counter()
        for key, e in zip(keys, exps):
            if op == "mod_exp":
                eng.warm(op, modulus=key.n, exponent=e)
            else:
                eng.warm(op, key=key)
        compile_s = time.perf_counter() - t0
        tiers = rep.tiers("modexp")
        traces0, batches0 = eng.stats.traces, eng.stats.batches
        reqs = []
        for i in range(REQUESTS):
            k = i % len(keys)
            key = keys[k]
            v = int.from_bytes(rng.bytes(nbytes), "little") % key.n
            reqs.append(BignumRequest(
                rid=i, op=op, value=api.to_limbs(v, RSA_BITS),
                key=None if op == "mod_exp" else key,
                modulus=key.n if op == "mod_exp" else None,
                exponent=exps[k] if op == "mod_exp" else None))
        t0 = time.perf_counter()
        for r in reqs:
            eng.submit(r, now=0.0)
        while eng.pending():
            eng.drain_one()
        steady_s = time.perf_counter() - t0
        ok = all(r.result is not None for r in reqs)
        for r in reqs if ok else ():
            key = r.key or keys[[k.n for k in keys].index(r.modulus)]
            v = to_ints(np.asarray(r.value)[None])[0]
            e = {"rsa_decrypt": key.d, "rsa_sign": key.d,
                 "rsa_verify": key.e, "mod_exp": r.exponent}[op]
            got = to_ints(np.asarray(r.result)[None])[0]
            ok &= got == pow(v, e, key.n)
        retraces = eng.stats.traces - traces0
        rep.phase(f"serve/{op}", ok, tiers, SERVE_TIERS[op], bits=RSA_BITS,
                  requests=REQUESTS, slots=eng.cfg.slots,
                  batches=eng.stats.batches - batches0,
                  degraded=eng.stats.degraded,
                  retraces_after_warm=retraces,
                  smoke_compile_seconds=round(compile_s, 3),
                  smoke_steady_seconds=round(steady_s, 3))
        if eng.stats.degraded:
            rep.errors.append(f"serve/{op}: {eng.stats.degraded} degraded")
        if retraces:
            rep.errors.append(f"serve/{op}: {retraces} retraces after warm")
    eng.close()


def timed(fn, *args):
    """(result, first-call seconds incl. compile, second-call seconds)."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    t1 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, t1 - t0, time.perf_counter() - t1


def arith_phase(api, rep: Report, seed: int) -> None:
    from repro.obs import trace as otrace

    rng = np.random.default_rng(seed + 100)
    for bits, batch, want in MUL_CASES:
        otrace.clear()
        a, b = random_limbs(rng, batch, bits), random_limbs(rng, batch, bits)
        out, c_s, s_s = timed(jax.jit(api.mul), a, b)
        ok = to_ints(out) == [x * y for x, y in zip(to_ints(a), to_ints(b))]
        rep.phase(f"mul/{bits}", ok, rep.tiers("mul"), want, bits=bits,
                  batch=batch, smoke_compile_seconds=round(c_s, 3),
                  smoke_steady_seconds=round(s_s, 3))
    for abits, bbits, batch, want in DIV_CASES:
        otrace.clear()
        a = random_limbs(rng, batch, abits)
        b = random_limbs(rng, batch, bbits)
        b[:, 0] |= 1                                # nonzero divisors
        (q, r), c_s, s_s = timed(jax.jit(api.divmod), a, b)
        ok = all((qq, rr) == divmod(x, y) for x, y, qq, rr in zip(
            to_ints(a), to_ints(b), to_ints(q), to_ints(r)))
        tiers = rep.tiers("div")
        mul_tiers = rep.tiers("mul")
        rep.phase(f"divmod/{abits}/{bbits}", ok, tiers, want,
                  bits=[abits, bbits], batch=batch,
                  mul_tiers=sorted(mul_tiers),
                  smoke_compile_seconds=round(c_s, 3),
                  smoke_steady_seconds=round(s_s, 3))
        if "recip" in want and not mul_tiers & KERNEL_MUL_TIERS:
            rep.errors.append(f"divmod/{abits}/{bbits}: its multiplies "
                              f"ran on no kernel tier {sorted(mul_tiers)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the keys, exponents and operands")
    args = ap.parse_args(argv)

    dev = require_tpu()
    api, use_compile_cache = import_repro()
    print(json.dumps({"phase": "setup", "compile_cache": use_compile_cache(),
                      "jax": jax.__version__}), flush=True)
    api.configure(kernel_fallback=False, observability=True)
    rep = Report(api)
    serve_phase(api, rep, args.seed)
    arith_phase(api, rep, args.seed)
    if rep.errors:
        for e in rep.errors:
            print(f"chip_smoke: FAILED {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
