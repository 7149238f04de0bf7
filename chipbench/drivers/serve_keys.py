"""Open-loop signing traffic under many keys through ``BignumEngine``.

The deployment of a TLS 1.3 terminator that holds ``keys`` certificates
of one width: each request's SNI picks a certificate, the hosted names'
popularity is Zipf-skewed (rank k drawn with weight ``1 / k**zipf_s``),
and the server signs under the picked key.  The arrivals, the engine
loop and the run's record are ``serve_open``'s own: ``schedule`` and
``window`` are imported unchanged.  Only the stream differs: each
request's key is drawn from the seed, and its message below that key's
n.

Every position of a stream has one key, drawn from the seed alone:
position i of ``values(n, salt)`` and request ``rid = i``, or
``rid = -1 - i`` (the negative rids of warm-up and of ``sweep.py``'s
capacity probe), name the same key, whatever the salt and n.

Key k is made from (seed, k) by the plain reference, in a few worker
processes (``KEYGEN_WORKERS``, started fresh: they touch no device), so
that ``setup_s`` is not mostly key generation.  Every key is registered
in the engine's key table before the one warm-up compile.

Before any compile, set-up checks that two requests under different keys
of one width fall in one engine bucket.  An engine that buckets by
modulus would compile a program a key and pad nearly every batch, and
fall behind all through the window: the run stops there, with a message.

Traffic parameters (the workload file's ``traffic``): ``op``
(``rsa_sign`` or ``rsa_verify``), ``rate_per_s``.  Configuration:
``key_bits``, ``e``, ``keys``, ``zipf_s``, ``serve`` (the
``ServeConfig`` fields), ``kernel_fallback``.
"""
from __future__ import annotations

import concurrent.futures
import itertools
import multiprocessing
import os
import random
import time

import numpy as np

from chipbench import reference
from chipbench.drivers import serve_open

schedule = serve_open.schedule
window = serve_open.window
KEYGEN_WORKERS = 12       # at most; a v5e host has 13 cores
DRAW_BLOCK = 4096         # key draws are made per block of positions


def make_keys(bits: int, e: int, count: int, seed: int) -> list:
    """``count`` RSA keys, key k made from (seed, k) alone, all distinct."""
    workers = min(KEYGEN_WORKERS, count, os.cpu_count() or 1)
    with concurrent.futures.ProcessPoolExecutor(
            workers, mp_context=multiprocessing.get_context("spawn")) as pool:
        keys = list(pool.map(reference.rsa_key, [bits] * count, [e] * count,
                             [f"{seed}/{k}" for k in range(count)]))
    if len({k["n"] for k in keys}) != count:
        raise SystemExit(f"chipbench serve_keys: seed {seed} gave two equal "
                         f"moduli among {count} keys")
    return keys


class Stream:
    """A run's keys and requests, from the seed alone (no engine)."""

    def __init__(self, config, op: str, seed: int):
        from repro import api
        from repro.serve.bignum_engine import BignumRequest

        self.Request = BignumRequest
        self.op, self.seed = op, seed
        self.keys = make_keys(config["key_bits"], config["e"],
                              config["keys"], seed)
        self.api_keys = [api.RSAKey(n=k["n"], e=k["e"], d=k["d"],
                                    bits=k["bits"], p=k["p"], q=k["q"])
                         for k in self.keys]
        self.nlimbs = -(-config["key_bits"] // 32)
        self.cum_weights = list(itertools.accumulate(
            1.0 / (k + 1) ** config["zipf_s"] for k in range(config["keys"])))
        self._draws: dict = {}

    def key_of(self, pos: int) -> int:
        """The key of stream position ``pos``: a Zipf draw."""
        block, at = divmod(pos, DRAW_BLOCK)
        if block not in self._draws:
            rng = random.Random(f"serve-keys/{self.seed}/{block}")
            self._draws[block] = rng.choices(
                range(len(self.keys)), cum_weights=self.cum_weights,
                k=DRAW_BLOCK)
        return self._draws[block][at]

    def values(self, n: int, salt: str) -> list:
        rng = random.Random(f"serve/{salt}/{self.seed}/{n}")
        return [rng.randrange(1, self.keys[self.key_of(i)]["n"])
                for i in range(n)]

    def request(self, rid: int, limbs):
        pos = rid if rid >= 0 else -1 - rid
        return self.Request(rid=rid, op=self.op, value=limbs,
                            key=self.api_keys[self.key_of(pos)])


class State(Stream):
    def __init__(self, config, traffic, seed, wrap=None):
        from repro import api
        from repro.configs.dot_bignum import ServeConfig
        from repro.serve.bignum_engine import BignumEngine

        api.configure(kernel_fallback=config["kernel_fallback"])
        super().__init__(config, traffic["op"], seed)
        self.rate = float(traffic["rate_per_s"])
        self.engine = BignumEngine(ServeConfig(**config["serve"]))
        a, b = (self.Request(rid=-1, op=self.op, value=None, key=k)
                for k in self.api_keys[:2])
        if self.engine.bucket_key(a) != self.engine.bucket_key(b):
            raise SystemExit(
                "chipbench serve_keys: the engine puts requests under two "
                "keys of one width in two buckets (a batch and a compiled "
                "program a key); this cell needs one bucket a width")
        if wrap is not None:
            self.engine._execute = wrap(self.engine._execute)
        self.engine.register_keys(self.api_keys)
        self.engine.warm(self.op, key=self.api_keys[0])
        warm = reference.to_limbs(
            self.values(serve_open.WARM_BATCHES * self.engine.cfg.slots,
                        salt="warm"), self.nlimbs)
        for i, v in enumerate(warm):
            self.engine.submit(self.request(-1 - i, v), now=time.perf_counter())
        while self.engine.pending():
            self.engine.drain_one()

    def close(self):
        self.engine.close()
        self.engine = None


def setup(config, traffic, seed, wrap=None):
    return State(config, traffic, seed, wrap)


def check(state: Stream, record: dict) -> dict:
    """Numbers compared with their limits: every answer the window
    served is compared with the reference under its own request's key
    (``s < n_k`` and ``s^e mod n_k == m``), and ``CHECK_SAMPLE`` answers
    drawn from the seed with ``m^d mod n_k`` itself."""
    idx = [i for i, r in enumerate(record["results"]) if r is not None]
    got = reference.to_ints(np.stack([record["results"][i] for i in idx])) \
        if idx else []
    rng = random.Random(f"serve/check/{state.seed}")
    sample = set(rng.sample(range(len(idx)),
                            min(serve_open.CHECK_SAMPLE, len(idx))))
    by_key: dict = {}
    for j, i in enumerate(idx):
        by_key.setdefault(state.key_of(i), []).append(j)
    wrong = sum(
        reference.rsa_wrong(state.op, [record["values"][idx[j]] for j in js],
                            [got[j] for j in js], state.keys[k],
                            [t for t, j in enumerate(js) if j in sample])
        for k, js in by_key.items())
    return {"wrong_answers": (wrong, 0), "lost_requests": (record["lost"], 0)}
