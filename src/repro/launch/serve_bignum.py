"""Crypto-serving entrypoint: Poisson request trace through the
continuous-batching BignumEngine, with the one-at-a-time NaiveServer
replayed on the same trace for comparison.

Usage:
  PYTHONPATH=src python -m repro.launch.serve_bignum \
      --bits 256 --requests 32 --rate 200 --slots 8 --op mixed
"""
from __future__ import annotations

import argparse
import copy
import random

from repro import api
from repro.configs.dot_bignum import SERVE, ServeConfig
from repro.kernels.common.runtime import use_compile_cache
from repro.serve.bignum_engine import (
    OPS, BignumEngine, NaiveServer, poisson_trace, replay_naive,
    replay_trace)


def build_ops(op: str, bits: int, groups: int, seed: int):
    """Request templates (dicts of BignumRequest kwargs) plus the warm
    list: ``groups`` distinct moduli/keys so the trace mixes shapes."""
    py = random.Random(seed)
    templates, warm = [], []
    if op in ("mod_exp", "mixed"):
        for g in range(groups):
            # distinct natural widths (bits, bits-16, ...) -> one bucket
            nb = bits - 16 * g
            n = py.getrandbits(nb) | 1 | (1 << (nb - 1))
            e = py.getrandbits(max(17, nb // 4)) | 1
            warm.append(dict(op="mod_exp", modulus=n, exponent=e))
            templates.append(dict(
                op="mod_exp", modulus=n, exponent=e,
                value=api.to_limbs(py.randrange(2, n), nb)))
    if op in ("rsa", "mixed"):
        key = api.generate_key(bits, seed=seed)
        msg = api.digest_int(b"serve_bignum", bits)
        for kind in ("rsa_sign", "rsa_verify", "rsa_decrypt"):
            warm.append(dict(op=kind, key=key))
            templates.append(dict(op=kind, key=key,
                                  value=api.to_limbs(msg, bits)))
    return templates, warm


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--bits", type=int, default=256)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=200.0,
                    help="Poisson arrival rate (requests/s, virtual clock)")
    ap.add_argument("--slots", type=int, default=SERVE.slots)
    ap.add_argument("--max-wait", type=float, default=SERVE.max_wait_s)
    ap.add_argument("--groups", type=int, default=2,
                    help="distinct moduli in the mod_exp mix")
    ap.add_argument("--op", default="mixed",
                    choices=("mixed", "rsa") + OPS)
    ap.add_argument("--backend", default=None,
                    help="modexp backend override (e.g. jnp)")
    ap.add_argument("--naive", action="store_true",
                    help="also replay the one-at-a-time baseline")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()

    templates, warm = build_ops(args.op, args.bits, args.groups, args.seed)
    trace = poisson_trace(templates, args.requests, args.rate,
                          seed=args.seed)

    cfg = ServeConfig(slots=args.slots, max_wait_s=args.max_wait)
    engine = BignumEngine(cfg, backend=args.backend)
    for w in warm:
        engine.warm(**w)
    warm_traces = engine.stats.traces

    res = replay_trace(engine, trace)
    st = engine.stats
    print(f"[serve_bignum] engine: {res.n} reqs in {res.makespan_s:.3f}s "
          f"= {res.ops_per_s:.1f} ops/s | p50 {res.p50_ms:.2f}ms "
          f"p99 {res.p99_ms:.2f}ms")
    print(f"[serve_bignum] engine: {st.batches} batches "
          f"({st.flush_full} full / {st.flush_deadline} deadline), "
          f"{st.padded_lanes} padded lanes, {st.programs} programs, "
          f"{st.traces - warm_traces} retraces after warm")

    if args.naive:
        naive = NaiveServer(backend=args.backend)
        nres = replay_naive(naive, copy.deepcopy(trace))
        print(f"[serve_bignum] naive:  {nres.n} reqs in "
              f"{nres.makespan_s:.3f}s = {nres.ops_per_s:.1f} ops/s | "
              f"p50 {nres.p50_ms:.2f}ms p99 {nres.p99_ms:.2f}ms "
              f"({naive.stats.traces} compiles in-trace)")
        print(f"[serve_bignum] engine vs naive throughput: "
              f"{res.ops_per_s / nres.ops_per_s:.2f}x")
    return res


if __name__ == "__main__":
    main()
