"""Open-loop serving traffic through ``BignumEngine``.

Requests arrive on a fixed schedule, whatever the engine does: a
generator thread releases request ``i`` at its scheduled time and
stamps when it really sent it.  The engine loop (the calling thread)
takes arrivals, calls ``submit`` and ``flush_next_due`` with the wall
clock, and stamps each completion.  A request's latency runs from its
scheduled send to its result, so a stall delays every request due
behind it.

The engine sees a request when the loop takes it (``submit`` with the
time of the call), as a server's engine sees what its loop has read
from the sockets: the backlog behind a flush waits in the arrival
queue, and the engine's deadlines start when it takes a request.  So
the engine's admission control (``max_queue``, a deadline slipped past
``max_wait_s``) does not fire in this loop and is outside what a cell
measures; a request it did shed would count as failed.

The schedule has Poisson arrivals at ``rate_per_s``: the ``n`` gaps are
the ``(i + 0.5) / n`` quantiles of the exponential distribution, in one
fixed shuffled order.  Every seed offers the same arrivals; the seed
draws the key and the messages.  (The tail of a window rests on its few
worst bursts: with an order drawn from the seed, p99 moved by about 13%
from seed to seed on one TPU v5e, while two runs of one seed agreed
within 4%.)

Traffic parameters (the workload file's ``traffic``): ``op``
(``rsa_sign`` or ``rsa_verify``), ``rate_per_s``.  Configuration:
``key_bits``, ``e``, ``serve`` (the ``ServeConfig`` fields).
"""
from __future__ import annotations

import math
import queue
import random
import sys
import threading
import time
from contextlib import nullcontext

import numpy as np

from chipbench import reference

WARM_BATCHES = 2          # full batches served in set-up, after warm()
CHECK_SAMPLE = 16         # signatures also compared with m ** d mod n


def schedule(rate: float, seconds: float) -> np.ndarray:
    """Send offsets in [0, seconds): Poisson arrivals at ``rate``."""
    n = max(1, round(rate * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    np.random.default_rng(n).shuffle(gaps)
    offsets = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return offsets * (seconds / gaps.sum())


class State:
    def __init__(self, config, traffic, seed, wrap=None):
        from repro import api
        from repro.configs.dot_bignum import ServeConfig
        from repro.serve.bignum_engine import BignumEngine, BignumRequest

        self.Request = BignumRequest
        api.configure(kernel_fallback=config["kernel_fallback"])
        self.op = traffic["op"]
        self.rate = float(traffic["rate_per_s"])
        self.seed = seed
        self.key = reference.rsa_key(config["key_bits"], config["e"], seed)
        k = self.key
        self.api_key = api.RSAKey(n=k["n"], e=k["e"], d=k["d"], bits=k["bits"],
                                  p=k["p"], q=k["q"])
        self.nlimbs = -(-k["bits"] // 32)
        self.engine = BignumEngine(ServeConfig(**config["serve"]))
        if wrap is not None:
            self.engine._execute = wrap(self.engine._execute)
        self.engine.warm(self.op, key=self.api_key)
        warm = reference.to_limbs(
            self.values(WARM_BATCHES * self.engine.cfg.slots, salt="warm"),
            self.nlimbs)
        for i, v in enumerate(warm):
            self.engine.submit(self.request(-1 - i, v), now=time.perf_counter())
        while self.engine.pending():
            self.engine.drain_one()

    def values(self, n: int, salt: str) -> list:
        rng = random.Random(f"serve/{salt}/{self.seed}/{n}")
        return [rng.randrange(1, self.key["n"]) for _ in range(n)]

    def request(self, rid: int, limbs):
        return self.Request(rid=rid, op=self.op, value=limbs, key=self.api_key)

    def close(self):
        self.engine.close()
        self.engine = None


def setup(config, traffic, seed, wrap=None):
    return State(config, traffic, seed, wrap)


def window(state: State, seconds: float, tracer=None,
           rate: float | None = None) -> dict:
    """Offer the schedule, serve until every request due has an answer,
    and return the run's record.  With a ``tracer`` the engine calls are
    annotated on the profiler's host timeline."""
    import jax

    rate = rate or state.rate
    sched = schedule(rate, seconds)
    n = len(sched)
    values = state.values(n, salt="window")
    limbs = reference.to_limbs(values, state.nlimbs)
    eng = state.engine
    stats0 = dict(vars(eng.stats))
    sent = np.full(n, np.nan)
    flush_start = np.full(n, np.nan)
    done = np.full(n, np.nan)
    shed = np.zeros(n, bool)
    results = [None] * n
    span = (jax.profiler.TraceAnnotation if tracer
            else lambda name: nullcontext())
    arrivals: queue.SimpleQueue = queue.SimpleQueue()
    clock = time.perf_counter
    t0 = clock() + 0.002
    due = t0 + sched

    def generate():
        for i in range(n):
            delay = due[i] - clock()
            if delay > 0:
                time.sleep(delay)
            sent[i] = clock()
            arrivals.put(i)
        arrivals.put(None)

    def finish(reqs, t_call):
        t_done = clock()
        for r in reqs:
            if r.shed:
                shed[r.rid] = True
            else:
                done[r.rid] = t_done
                flush_start[r.rid] = t_call
                results[r.rid] = r.result

    gen = threading.Thread(target=generate, name="chipbench-arrivals")
    gen.start()
    generating = True
    try:
        while generating or eng.pending():
            now = clock()
            if tracer:
                tracer.poll(t0, now)
            nd = eng.next_deadline()
            if nd is not None and nd <= now:
                with span("bench.flush_due"):
                    finish(eng.flush_next_due(now), now)
                continue
            timeout = None if nd is None else nd - now
            if not generating:
                with span("bench.wait_deadline"):
                    time.sleep(timeout)
                continue
            try:
                with span("bench.wait_arrival"):
                    i = arrivals.get(timeout=timeout)
            except queue.Empty:
                continue
            if i is None:
                generating = False
                continue
            t_call = clock()
            with span("bench.submit"):
                finish(eng.submit(state.request(i, limbs[i]), now=t_call),
                       t_call)
    finally:
        gen.join()
    stats = {k: v - stats0[k] for k, v in vars(eng.stats).items()}
    served = ~np.isnan(done)
    if served.sum() > 1:
        ends = np.sort(done[served])
        gap = int(np.argmax(np.diff(ends)))
        print(f"chipbench serve: longest gap between answers "
              f"{ends[gap + 1] - ends[gap]:.4f} s at {ends[gap] - t0:.2f} s; "
              f"latest send {np.nanmax(sent - due):.4f} s behind schedule",
              file=sys.stderr)
    return {
        "kind": "serve", "t0": t0, "window_s": float(seconds),
        "attempted": n, "shed": int(shed.sum()),
        "lost": int(n - served.sum() - shed.sum()),
        "latency_s": np.where(served, done - due, math.inf).tolist(),
        "send_lag_s": (sent - due).tolist(),
        "queue_wait_s": (flush_start - due)[served].tolist(),
        "completed_in_window": int((done <= t0 + seconds).sum()),
        "drain_s": float(np.nanmax(done) - (t0 + sched[-1])) if served.any()
        else math.inf,
        "done_at": [(t, 1) for t in done[served].tolist()],
        "engine": stats, "slots": eng.cfg.slots,
        "values": values, "results": results,
    }


def check(state: State, record: dict) -> dict:
    """Numbers compared with their limits: every answer the window
    served is compared with the reference."""
    idx = [i for i, r in enumerate(record["results"]) if r is not None]
    values = [record["values"][i] for i in idx]
    got = reference.to_ints(np.stack([record["results"][i] for i in idx])) \
        if idx else []
    rng = random.Random(f"serve/check/{state.seed}")
    sample = rng.sample(range(len(idx)), min(CHECK_SAMPLE, len(idx)))
    wrong = reference.rsa_wrong(state.op, values, got, state.key, sample)
    return {"wrong_answers": (wrong, 0), "lost_requests": (record["lost"], 0)}
