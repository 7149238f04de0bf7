"""Closed-loop batch arithmetic: one caller calls the jitted ``api.mul``
back to back and blocks on each product, as a program that uses each
product before it asks for the next.

Operands are ``operand_sets`` pairs of (batch, bits / 32) uint32 arrays,
made on the device from the seed in one jitted call during set-up; call
``i`` multiplies pair ``i % operand_sets``.  The products of a few calls
drawn from the seed, and of the last call, are kept and compared with
python ints once the window has closed.

Traffic parameters: ``bits``, ``batch``, ``operand_sets``.
"""
from __future__ import annotations

import time
from contextlib import nullcontext

import numpy as np

from chipbench import reference

SAMPLED_CALLS = 3         # calls drawn from the seed, besides the last


class State:
    def __init__(self, config, traffic, seed, wrap=None):
        import jax
        import jax.numpy as jnp
        from repro import api

        api.configure(kernel_fallback=config["kernel_fallback"])
        self.bits, self.batch = int(traffic["bits"]), int(traffic["batch"])
        if self.bits not in config["operand_bits"]:
            raise ValueError(f"{self.bits} bits is not on the grid of the "
                             f"configuration: {config['operand_bits']}")
        self.seed = seed
        sets, m = int(traffic["operand_sets"]), self.bits // 32
        words = np.random.SeedSequence(seed).generate_state(2)

        @jax.jit
        def make(k):
            ks = jax.random.split(k, 2 * sets)
            return [jax.random.bits(ks[i], (self.batch, m), jnp.uint32)
                    for i in range(2 * sets)]

        key = jax.random.fold_in(jax.random.key(int(words[0])), int(words[1]))
        ops = make(key)
        self.pairs = list(zip(ops[:sets], ops[sets:]))
        self.fn = jax.jit(api.mul)
        if wrap is not None:
            self.fn = wrap(self.fn)
        for a, b in self.pairs + self.pairs[:1]:
            t = time.perf_counter()
            jax.block_until_ready(self.fn(a, b))
        self.call_s = time.perf_counter() - t     # one warm call

    def close(self):
        self.pairs = self.fn = None


def setup(config, traffic, seed, wrap=None):
    return State(config, traffic, seed, wrap)


def window(state: State, seconds: float, tracer=None) -> dict:
    import jax

    est_calls = max(1, int(seconds / max(state.call_s, 1e-6)))
    rng = np.random.default_rng([state.seed, 7])
    sample = set(rng.integers(0, est_calls, SAMPLED_CALLS).tolist())
    span = (jax.profiler.TraceAnnotation if tracer
            else lambda name: nullcontext())
    kept, calls, host_s, done_at = {}, 0, 0.0, []
    pairs, fn, nsets = state.pairs, state.fn, len(state.pairs)
    clock = time.perf_counter
    t0 = clock()
    while True:
        a, b = pairs[calls % nsets]
        t = clock()
        if tracer:
            tracer.poll(t0, t)
        with span("bench.call"):
            out = fn(a, b)
        t1 = clock()
        with span("bench.block"):
            jax.block_until_ready(out)
        t2 = clock()
        host_s += t1 - t
        done_at.append((t2, state.batch))
        if calls in sample:
            kept[calls] = out
        last = out
        calls += 1
        if t2 - t0 >= seconds:
            break
    kept[calls - 1] = last
    window_s = t2 - t0
    products = {c: np.asarray(o) for c, o in kept.items()}
    operands = {c % nsets: (np.asarray(pairs[c % nsets][0]),
                            np.asarray(pairs[c % nsets][1])) for c in kept}
    return {
        "kind": "arith", "t0": t0, "window_s": window_s, "calls": calls,
        "attempted": calls * state.batch, "ops_done": calls * state.batch,
        "host_call_s": host_s, "done_at": done_at, "products": products,
        "operands": operands, "nsets": nsets,
    }


def check(state: State, record: dict) -> dict:
    """Every row of every kept product is compared with python ints."""
    wrong = 0
    for c, out in record["products"].items():
        a, b = record["operands"][c % record["nsets"]]
        wrong += reference.mul_wrong(a, b, out)
    return {"wrong_products": (wrong, 0)}
