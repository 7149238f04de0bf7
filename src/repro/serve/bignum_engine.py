"""Request-level continuous batching for large-number crypto ops.

The LM ServeEngine (serve/engine.py) batches token streams; this engine
batches *arithmetic requests*: independent RSA sign / verify / decrypt
and raw mod_exp calls arriving one at a time are aggregated into padded
``slots``-lane batches so the fused windowed ladder runs in its
``MODEXP_DISPATCH.fused_min_batch`` regime instead of at batch 1.

Two mechanisms make an arbitrary request mix serve from a FINITE set of
compiled programs (the retrace economics that motivate the design: a
fresh XLA trace of a 1024-bit ladder costs seconds on this grid, the op
itself milliseconds):

* **Shape bucketing** -- a request's modulus width is quantized up to a
  ``ServeConfig.bucket_bits`` tier (raw mod_exp exponent widths to
  ``exp_bucket_bits``), so arbitrary natural widths collapse onto a few
  padded shapes.  RSA-key ops keep their natural width: the key set is
  finite, so it is already a finite shape set.
* **One program per key width** -- ``rsa_sign`` / ``rsa_verify`` lanes
  carry their own keys: the Montgomery ladder takes every lane's modulus
  and constants as run-time operands (kernels/dot_modmul), so the bucket
  key is ``(op, bits)`` and requests under different keys of one width
  share batches and one compiled program.  The keys live in a table on
  the device, one per width (``register_keys``, one row a key, grown in
  powers of two); a flush sends the lanes' key indices and the program
  gathers their rows.  ``rsa_decrypt`` (two CRT moduli a key) and raw
  ``mod_exp`` keep one bucket and one program per modulus:
  ``(op, width-bucket, exp-bucket, modulus)``.  ``warm()`` pre-compiles
  before traffic; register every key first and one compile serves them.

Batching policy (continuous): requests queue per bucket key; a bucket
flushes when it reaches ``slots`` lanes (full flush) or when its oldest
request has waited ``max_wait_s`` (deadline flush, padded by repeating
lane 0).  ``replay_trace`` replays a timed arrival trace against the
engine event by event -- virtual arrival clock, real measured service
times, single serial device -- and ``NaiveServer`` / ``replay_naive``
is the one-request-at-a-time natural-shape baseline the benchmarks
compare against.

Fault tolerance (PR 9)
----------------------
The engine assumes failures and bounds them instead of crashing:

* **Admission control / shedding** -- ``submit`` rejects on arrival
  (``req.shed = True``, ``shed_total`` ticks, request completes with no
  result) when the queue exceeds ``ServeConfig.max_queue`` or the
  oldest deadline has slipped more than ``max_wait_s`` past due, so a
  burst degrades to bounded rejections, not unbounded latency.
* **Deadline accounting** -- a request carrying ``sla_s`` that
  completes later than that ticks ``deadline_miss_total{op,bits}``.
* **Retry + degrade** -- a flush that raises is retried up to
  ``max_retries`` (exponential backoff from ``retry_backoff_s``); when
  retries exhaust and ``configure(kernel_fallback=True)`` is on (it is
  off by default, and the error then propagates), the bucket is
  DEGRADED one backend tier
  (auto/pallas -> jnp -> host reference) and re-run, ticking
  ``fallback_total{op,backend,reason=flush_*}``.  The recompile a
  degrade forces is expected, so it does not trip the retrace alarm.
* **Partial-failure warm()** -- a bucket whose warm-up fails degrades
  the same way instead of failing the whole warm pass; warm is also
  idempotent per bucket (re-warming is a no-op, not a jit-cache leak).
* **Graceful shutdown** -- ``close()`` drains pending queues, then
  marks the engine terminal: submit/warm after close raise a clear
  RuntimeError instead of leaking state.
* **Residue self-checking** -- under ``configure(selfcheck=...)``
  every real lane of every flush is verified against a host witness
  (public-exponent re-encryption for sign/decrypt, pow() recompute
  otherwise -- see repro/resilience/selfcheck.py); a corrupted lane is
  REPAIRED from the witness before results are returned, ticking
  ``selfcheck_failures_total`` and applying the warn/raise policy.

Spans and counters
------------------
Every ``submit`` runs in a ``serve.submit`` span and every flush in a
``serve.flush`` span (args: a per-engine ``flush`` sequence number,
``op``, ``bits``, ``lanes``, ``keys`` -- distinct keys or moduli among
the lanes --, ``reason``, the served ``rids``, and ``compiled=True``
when the jit cache missed), which holds
``serve.assemble`` (the padded batch), ``serve.dispatch`` (the jitted
call), ``serve.device_wait`` (blocking on the result) and
``serve.unpack`` (checks and hand-out).  Key registration and the
table's upload run in a ``serve.keys`` span (args ``keys``, ``rows``).
``EngineStats`` adds up the engine's own waits on its own clock: see its
docstring.

All arithmetic goes through the ``repro.api`` facade; this module never
imports the digit-radix internals.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro import api, obs
from repro.configs.dot_bignum import SERVE, ServeConfig, quantize_bits
from repro.obs import metrics as _metrics
from repro.obs import retrace as _retrace
from repro.resilience import guard as _guard
from repro.resilience import inject as _inject
from repro.resilience import selfcheck as _selfcheck

OPS = ("mod_exp", "rsa_sign", "rsa_verify", "rsa_decrypt")
KEYED_OPS = ("rsa_sign", "rsa_verify")    # a key a lane, from the key table

# (op, width bucket bits, exp bucket bits or None, modulus / key.n; None
# for KEYED_OPS, whose lanes carry their own keys)
BucketKey = Tuple[str, int, Optional[int], Optional[int]]


@dataclasses.dataclass
class BignumRequest:
    """One crypto call.  ``value`` is the natural-width uint32 limb
    vector of the operand (mod_exp base, message, signature, or
    ciphertext); ``modulus`` + ``exponent`` (python ints) for op
    "mod_exp", ``key`` for the rsa_* ops.  The engine fills
    ``arrival`` / ``deadline`` / ``completion`` / ``result``."""

    rid: int
    op: str
    value: np.ndarray
    modulus: Optional[int] = None
    exponent: Optional[int] = None
    key: Optional[api.RSAKey] = None
    sla_s: Optional[float] = None       # per-request latency SLA
    arrival: float = 0.0
    deadline: float = 0.0
    queued_at: float = 0.0              # engine clock when submit queued it
    completion: Optional[float] = None
    result: Optional[np.ndarray] = None
    shed: bool = False                  # rejected at admission (no result)

    @property
    def latency(self) -> float:
        if self.completion is None:
            raise ValueError(f"request {self.rid} not served yet")
        return self.completion - self.arrival


@dataclasses.dataclass
class EngineStats:
    """Counters of one engine since it was made.  Every field is a plain
    number, so the difference of two snapshots, field by field, is what
    happened between them (no lists, dicts or maxima here).  The
    ``*_s`` fields are seconds on the engine's own ``time.perf_counter``
    clock, never the callers' ``now``."""

    traces: int = 0           # jit cache misses (python body executions)
    programs: int = 0         # distinct compiled entry points
    served: int = 0
    batches: int = 0
    batch_keys: int = 0       # per flush: distinct keys (moduli) of its lanes
    flush_full: int = 0
    flush_deadline: int = 0
    padded_lanes: int = 0
    shed: int = 0             # requests rejected at admission
    retries: int = 0          # flush attempts repeated after a failure
    degraded: int = 0         # bucket backend-tier demotions
    deadline_misses: int = 0  # requests completing past their sla_s
    selfcheck_failures: int = 0   # lanes caught (and repaired) by selfcheck
    wait_s: float = 0.0           # per served request: flush start - queued
    flush_s: float = 0.0          # wall time of the flushes that served
    device_wait_s: float = 0.0    # blocked on device results (warm too)


class BignumEngine:
    """Continuous-batching server for the ops in ``OPS``.

    The event API is clock-explicit so replays and tests are
    deterministic: callers pass virtual times in, and every method that
    may run device work returns the list of requests it completed
    (empty when it only queued).  ``submit`` flushes on batch-full;
    ``flush_next_due`` serves the earliest expired deadline;
    ``drain_one`` force-flushes when the trace is over."""

    def __init__(self, cfg: Optional[ServeConfig] = None, *,
                 backend: Optional[str] = None):
        self.cfg = cfg or SERVE
        self.backend = backend
        self.stats = EngineStats()
        self._queues: Dict[BucketKey, List[BignumRequest]] = {}
        self._deadlines: Dict[BucketKey, float] = {}
        self._fns: Dict[BucketKey, Callable] = {}
        self._ctxs: Dict[Tuple[int, int], object] = {}
        # KEYED_OPS: per key width, the keys in table order, each key's
        # row, and the device-resident table (api.KeyLanes)
        self._keys: Dict[int, List[api.RSAKey]] = {}
        self._key_row: Dict[api.RSAKey, int] = {}
        self._tables: Dict[int, api.KeyLanes] = {}
        # the zero-retrace contract arms once warm() completes: any jit
        # body execution after that is an unexpected retrace
        self._warmed = False
        self._warmed_keys: set = set()      # warm() idempotence, by bucket
        self._degraded: Dict[BucketKey, str] = {}   # bucket -> demoted tier
        self._expect_trace = False          # a degrade's recompile is legit
        self._closed = False
        self._flush_seq = 0                 # serve.flush span ids

    # -- bucketing --------------------------------------------------------

    def bucket_key(self, req: BignumRequest) -> BucketKey:
        """Quantized jit-cache key for a request (public for tests)."""
        if req.op not in OPS:
            raise ValueError(
                f"unknown serve op {req.op!r}; choose from {OPS}")
        if req.op == "mod_exp":
            if req.modulus is None or req.exponent is None:
                raise ValueError(
                    "mod_exp requests need modulus= and exponent=")
            nbits = quantize_bits(req.modulus.bit_length(),
                                  self.cfg.bucket_bits)
            ebits = quantize_bits(max(1, req.exponent.bit_length()),
                                  self.cfg.exp_bucket_bits)
            return (req.op, nbits, ebits, req.modulus)
        if req.key is None:
            raise ValueError(f"{req.op} requests need key=")
        if req.op in KEYED_OPS:
            return (req.op, req.key.bits, None, None)
        return (req.op, req.key.bits, None, req.key.n)

    def _ctx(self, modulus: int, nbits: int):
        k = (modulus, nbits)
        if k not in self._ctxs:
            self._ctxs[k] = api.mod_setup(modulus, nbits)
        return self._ctxs[k]

    # -- key tables -------------------------------------------------------

    def register_keys(self, keys) -> None:
        """Add RSA keys to the device-resident key table of their width,
        which every ``rsa_sign`` / ``rsa_verify`` program of that width
        reads.  The table holds one row a key (modulus, R^2 mod n, n0p,
        the bits of d and e) and grows in powers of two: a registration
        that outgrows it changes the programs' input shape, so the next
        flush compiles anew (register every key before ``warm()``).
        Registered keys are kept; a flush registers its lanes' keys."""
        new = [k for k in dict.fromkeys(keys) if k not in self._key_row]
        if not new:
            return
        for bits in sorted({k.bits for k in new}):
            rows = self._keys.setdefault(bits, [])
            for k in new:
                if k.bits == bits:
                    self._key_row[k] = len(rows)
                    rows.append(k)
            size = 1 << (len(rows) - 1).bit_length()
            with obs.span("serve.keys", keys=len(rows), rows=size):
                table = api.key_lanes(rows + rows[:1] * (size - len(rows)))
                self._tables[bits] = jax.device_put(table)

    def _warm_mark(self, bkey: BucketKey):
        """What a warmed program is valid for: its bucket, and for
        KEYED_OPS the rows of the key table it was compiled at."""
        if bkey[0] in KEYED_OPS:
            return bkey, self._tables[bkey[1]].n0p.shape[0]
        return bkey

    # -- compiled-program cache -------------------------------------------

    def _fn(self, bkey: BucketKey, sample: BignumRequest) -> Callable:
        if bkey in self._fns:
            return self._fns[bkey]
        op, nbits, _, _ = bkey
        stats = self.stats
        backend = self._degraded.get(bkey, self.backend)
        engine = self
        if op == "mod_exp":
            ctx = self._ctx(sample.modulus, nbits)

            def body(base, exp_bits, _ctx=ctx):
                stats.traces += 1
                engine._on_trace(op, nbits)
                return api.mod_exp(base, exp_bits, _ctx, backend=backend)
        elif op == "rsa_decrypt":
            key, crt = sample.key, sample.key.p != 0

            def body(base, _key=key, _crt=crt):
                stats.traces += 1
                engine._on_trace(op, nbits)
                return api.rsa_decrypt(base, _key, backend=backend,
                                       crt=_crt)
        else:
            f = api.rsa_sign if op == "rsa_sign" else api.rsa_verify

            def body(base, rows, table, _f=f):
                # the table is an argument, not a constant of the
                # program: one program serves every key of the width
                stats.traces += 1
                engine._on_trace(op, nbits)
                return _f(base, table.take(rows), backend=backend)
        fn = jax.jit(body)
        self._fns[bkey] = fn
        stats.programs += 1
        return fn

    def _on_trace(self, op: str, nbits: int) -> None:
        """Python-side hook inside every jitted body: runs exactly on
        jit cache misses (fresh XLA traces).  After ``warm()`` has
        completed, any execution here breaks the zero-retrace contract
        -- tick the ``retraces_total`` metric and apply the configured
        ``on_retrace`` policy (repro/obs/retrace.py).  The one expected
        post-warm trace is the recompile a backend-tier degrade forces
        (``_expect_trace``); it is deliberate, not a contract break."""
        if self._warmed and not self._expect_trace:
            _retrace.alarm("serve", op=op, bits=nbits)

    def _execute(self, bkey: BucketKey,
                 reqs: List[BignumRequest]) -> np.ndarray:
        """Pad ``reqs`` to a full ``slots`` batch and run the bucket's
        compiled program; returns the (slots, limbs) result block."""
        op, nbits, ebits, _ = bkey
        slots = self.cfg.slots
        with obs.span("serve.assemble"):
            fn = self._fn(bkey, reqs[0])
            lw = (nbits // 32 if op == "mod_exp"
                  else -(-reqs[0].key.bits // 32))
            base = np.zeros((slots, lw), np.uint32)
            for i, r in enumerate(reqs):
                v = np.asarray(r.value, np.uint32).reshape(-1)
                base[i, : v.size] = v
            base[len(reqs):] = base[0]              # pad: repeat lane 0
            args = (base,)
            if op == "mod_exp":
                rows = [np.asarray(api.exp_bits_msb(r.exponent, ebits))
                        for r in reqs]
                rows += [rows[0]] * (slots - len(reqs))
                args += (np.stack(rows),)
            elif op in KEYED_OPS:
                self.register_keys([r.key for r in reqs])
                rows = [self._key_row[r.key] for r in reqs]
                rows += rows[:1] * (slots - len(reqs))   # pad: lane 0's key
                args += (np.asarray(rows, np.int32), self._tables[nbits])
        with obs.span("serve.dispatch"):
            out = fn(*args)
        t0 = time.perf_counter()
        with obs.span("serve.device_wait"):
            out = np.asarray(jax.block_until_ready(out))
        self.stats.device_wait_s += time.perf_counter() - t0
        return out

    # -- degradation ------------------------------------------------------

    def _tier_name(self, bkey: BucketKey) -> str:
        """Label of the backend tier this bucket currently runs at."""
        return self._degraded.get(bkey) or self.backend or "auto"

    def _next_tier(self, bkey: BucketKey) -> Optional[str]:
        """One step down the degradation ladder for this bucket, or
        None when the bucket already runs at the host-reference floor."""
        cur = self._degraded.get(bkey)
        if cur is None:
            return "reference" if self.backend == "jnp" else "jnp"
        if cur == "jnp":
            return "reference"
        return None

    def _degrade(self, bkey: BucketKey, exc: BaseException,
                 phase: str) -> bool:
        """Demote the bucket one tier after ``exc``; False when there is
        no tier left or ``configure(kernel_fallback=...)`` is off (the
        default: the failure then propagates).  Drops the bucket's
        compiled program so the next run retraces at the demoted
        backend (an EXPECTED trace)."""
        nxt = self._next_tier(bkey)
        if nxt is None or not _guard.fallback_enabled():
            return False
        _guard.tick(bkey[0], self._tier_name(bkey),
                    f"{phase}_{_guard.classify(exc)}")
        self.stats.degraded += 1
        self._degraded[bkey] = nxt
        self._fns.pop(bkey, None)
        return True

    def _execute_reference(self, bkey: BucketKey,
                           reqs: List[BignumRequest]) -> np.ndarray:
        """The host floor of the degradation ladder: python-int math per
        real lane, no jit, cannot fail on device state.  Same (slots,
        limbs) block contract as ``_execute`` (padded lanes zero)."""
        op, nbits, _, _ = bkey
        slots = self.cfg.slots
        lw = nbits // 32 if op == "mod_exp" else -(-reqs[0].key.bits // 32)
        out = np.zeros((slots, lw), np.uint32)
        for i, r in enumerate(reqs):
            v = api.from_limbs(np.asarray(r.value, np.uint32).reshape(-1))
            res = _selfcheck.repair_lane(
                op, v, modulus=r.modulus, exponent=r.exponent, key=r.key)
            out[i] = api.to_limbs(res, 32 * lw)
        return out

    def _run_batch(self, bkey: BucketKey,
                   reqs: List[BignumRequest]) -> np.ndarray:
        """Execute one batch with bounded retry, then degrade-and-rerun:
        transient failures get ``max_retries`` attempts (exponential
        backoff); a persistent failure demotes the bucket's backend tier
        and starts over.  Every request that enters here leaves with a
        result unless even the host-reference floor raises."""
        attempt = 0
        while True:
            try:
                _inject.fire(f"serve/flush/{bkey[0]}")
                if self._degraded.get(bkey) == "reference":
                    out = self._execute_reference(bkey, reqs)
                else:
                    out = self._execute(bkey, reqs)
                self._expect_trace = False
                return out
            except Exception as exc:                # noqa: BLE001
                if attempt < self.cfg.max_retries:
                    attempt += 1
                    self.stats.retries += 1
                    if self.cfg.retry_backoff_s:
                        time.sleep(
                            self.cfg.retry_backoff_s * 2 ** (attempt - 1))
                    continue
                if not self._degrade(bkey, exc, "flush"):
                    raise
                self._expect_trace = True
                attempt = 0

    # -- serving ----------------------------------------------------------

    def warm(self, op: str, *, modulus: Optional[int] = None,
             exponent: Optional[int] = None,
             key: Optional[api.RSAKey] = None) -> None:
        """Pre-compile the program for one bucket before traffic (for
        mod_exp, ``exponent`` is a representative value -- only its
        quantized width matters; for rsa_sign / rsa_verify, ``key`` is
        registered and the program serves every registered key of its
        width).  Serving a warmed bucket never traces again: snapshot
        ``stats.traces`` after warming to assert the zero-retrace
        property (the runtime form of the same contract is the retrace
        alarm, armed once any warm() finishes -- see ``_on_trace``).

        Idempotent per bucket (re-warming a warmed key is a no-op, not a
        fresh trace).  A bucket whose warm-up raises propagates the
        error, unless ``configure(kernel_fallback=True)`` is on: it is
        then demoted a backend tier and re-warmed, and warm only raises
        when even the host-reference floor fails."""
        if self._closed:
            raise RuntimeError(
                "BignumEngine is closed; warm() after close() is invalid "
                "-- create a new engine")
        sample = BignumRequest(rid=-1, op=op, value=np.zeros(1, np.uint32),
                               modulus=modulus, exponent=exponent, key=key)
        bkey = self.bucket_key(sample)
        if op in KEYED_OPS:
            self.register_keys([key])
        mark = self._warm_mark(bkey)
        if mark in self._warmed_keys:
            return
        self._warmed = False            # warming traces are expected
        try:
            while True:
                try:
                    if self._degraded.get(bkey) == "reference":
                        self._execute_reference(bkey, [sample])
                    else:
                        self._execute(bkey, [sample])
                    break
                except Exception as exc:            # noqa: BLE001
                    if not self._degrade(bkey, exc, "warm"):
                        raise
            self._warmed_keys.add(mark)
        finally:
            self._warmed = True

    def submit(self, req: BignumRequest, now: float = 0.0
               ) -> List[BignumRequest]:
        """Enqueue; flushes and returns the batch when it fills.

        Admission control runs first: when the engine is overloaded
        (queue depth >= ``max_queue``, or the oldest pending deadline
        has slipped more than ``max_wait_s`` past due) the request is
        SHED -- returned immediately with ``shed=True`` and no result,
        ticking ``shed_total{op}`` -- so overload degrades to bounded,
        observable rejections instead of unbounded queue growth."""
        with obs.span("serve.submit", rid=req.rid):
            return self._submit(req, now)

    def _submit(self, req: BignumRequest, now: float
                ) -> List[BignumRequest]:
        if self._closed:
            raise RuntimeError(
                "BignumEngine is closed; submit() after close() is "
                "invalid -- create a new engine")
        bkey = self.bucket_key(req)
        req.arrival = now
        req.deadline = now + self.cfg.max_wait_s
        nd = self.next_deadline()
        if (self.pending() >= self.cfg.max_queue
                or (nd is not None and now - nd > self.cfg.max_wait_s)):
            req.shed = True
            self.stats.shed += 1
            _metrics.REGISTRY.counter(
                "shed_total", "requests rejected at admission").inc(
                op=req.op)
            return [req]
        q = self._queues.setdefault(bkey, [])
        req.queued_at = time.perf_counter()
        q.append(req)
        if len(q) == 1:
            self._deadlines[bkey] = req.deadline
        if len(q) >= self.cfg.slots:
            return self._flush(bkey, "full", now)
        return []

    def close(self, drain: bool = True) -> List[BignumRequest]:
        """Graceful shutdown: drain every pending bucket (serving the
        queued requests), then mark the engine terminal.  With
        ``drain=False`` pending requests are returned UNSERVED (shed)
        instead of executed.  Idempotent; after close, submit()/warm()
        raise RuntimeError."""
        if self._closed:
            return []
        done: List[BignumRequest] = []
        if drain:
            while self.pending():
                done += self.drain_one()
        else:
            for q in self._queues.values():
                for r in q:
                    r.shed = True
                    self.stats.shed += 1
                done += q
            self._queues.clear()
            self._deadlines.clear()
        self._closed = True
        return done

    def next_deadline(self) -> Optional[float]:
        return min(self._deadlines.values(), default=None)

    def flush_next_due(self, now: float) -> List[BignumRequest]:
        """Serve the earliest bucket whose deadline has expired."""
        due = [(dl, k) for k, dl in self._deadlines.items() if dl <= now]
        if not due:
            return []
        _, bkey = min(due, key=lambda t: t[0])
        return self._flush(bkey, "deadline", now)

    def drain_one(self) -> List[BignumRequest]:
        """Force-flush one pending bucket (oldest deadline first)."""
        if not self._deadlines:
            return []
        bkey = min(self._deadlines, key=self._deadlines.get)
        return self._flush(bkey, "deadline", self._deadlines[bkey])

    def pending(self) -> int:
        return sum(len(q) for q in self._queues.values())

    def _flush(self, bkey: BucketKey, reason: str,
               now: Optional[float] = None) -> List[BignumRequest]:
        t_start = time.perf_counter()
        reqs = self._queues.pop(bkey)
        deadline = self._deadlines.pop(bkey, None)
        traces0 = self.stats.traces
        self._flush_seq += 1
        with obs.span("serve.flush", flush=self._flush_seq, op=bkey[0],
                      bits=bkey[1], lanes=len(reqs),
                      keys=_distinct_keys(reqs), reason=reason,
                      rids=" ".join(str(r.rid) for r in reqs)) as args:
            try:
                out = self._run_batch(bkey, reqs)
            except Exception:
                # retries and degradation are exhausted: put the batch
                # back so close()/drain keep seeing it, then let the
                # error surface
                self._queues[bkey] = reqs
                if deadline is not None:
                    self._deadlines[bkey] = deadline
                raise
            dt = time.perf_counter() - t_start
            if self.stats.traces > traces0:
                args["compiled"] = True
            with obs.span("serve.unpack"):
                self._unpack(bkey, reqs, reason, out, now, dt)
        st = self.stats
        st.wait_s += sum(t_start - r.queued_at for r in reqs)
        st.flush_s += time.perf_counter() - t_start
        return list(reqs)

    def _unpack(self, bkey: BucketKey, reqs: List[BignumRequest],
                reason: str, out: np.ndarray, now: Optional[float],
                dt: float) -> None:
        """Fault injection, self-check and the hand-out of a flushed
        batch's results; the flush's counters."""
        op = bkey[0]
        # result-trimmed region: mod_exp pads to the bucket width but only
        # the natural modulus width is returned (all requests in a bucket
        # share bkey[3] = modulus / key.n), rsa_* returns full key width
        trim = (-(-bkey[3].bit_length() // 32) if op == "mod_exp"
                else out.shape[-1])
        view = out[:, :trim]
        sub = _inject.corrupt(f"serve/flush/{op}", view, len(reqs))
        if sub is not view:                      # fault injected: flipped
            out = np.array(out)                  # one bit of one real lane
            out[:, :trim] = sub
        if _selfcheck.enabled():
            out = self._selfcheck_batch(bkey, reqs, out, trim)
        for i, r in enumerate(reqs):
            r.result = out[i, :trim] if op == "mod_exp" else out[i]
        st = self.stats
        st.served += len(reqs)
        st.batches += 1
        st.batch_keys += _distinct_keys(reqs)
        st.padded_lanes += self.cfg.slots - len(reqs)
        if reason == "full":
            st.flush_full += 1
        else:
            st.flush_deadline += 1
        for r in reqs:
            if r.sla_s is None:
                continue
            wait = max(0.0, now - r.arrival) if now is not None else 0.0
            if wait + dt > r.sla_s:
                st.deadline_misses += 1
                _metrics.REGISTRY.counter(
                    "deadline_miss_total",
                    "served requests whose latency exceeded sla_s").inc(
                    op=op, bits=bkey[1])
        if obs.enabled():
            self._observe_latency(bkey, reqs, now, dt)

    def _selfcheck_batch(self, bkey: BucketKey, reqs: List[BignumRequest],
                         out: np.ndarray, trim: int) -> np.ndarray:
        """Residue/witness-verify every REAL lane of a flushed batch and
        repair mismatches from the host-int reference before results are
        handed out.  Each bad lane ticks ``selfcheck_failures_total``
        and ``fallback_total{reason="selfcheck"}``; the configured
        policy (warn/raise) fires AFTER repair, so even "raise" callers
        can recover served-but-flagged results from the request
        objects."""
        op, nbits, _, _ = bkey
        bad = 0
        for i, r in enumerate(reqs):
            v = api.from_limbs(np.asarray(r.value, np.uint32).reshape(-1))
            res = api.from_limbs(out[i, :trim])
            if _selfcheck.verify_lane(op, v, res, modulus=r.modulus,
                                      exponent=r.exponent, key=r.key):
                continue
            if bad == 0:
                out = np.array(out)
            bad += 1
            good = _selfcheck.repair_lane(op, v, modulus=r.modulus,
                                          exponent=r.exponent, key=r.key)
            out[i, :trim] = api.to_limbs(good, 32 * trim)
        if bad:
            self.stats.selfcheck_failures += bad
            _guard.tick(op, self._tier_name(bkey), "selfcheck", amount=bad)
            _selfcheck.report(op, bad, "serve flush lane verification",
                              bits=nbits)
        return out

    def _observe_latency(self, bkey: BucketKey, reqs: List[BignumRequest],
                         now: Optional[float], dt: float) -> None:
        """Observe each request's latency in the
        ``serve_request_latency_seconds`` histogram (observability on).

        Latency = virtual queue wait (``now`` - arrival, on the caller's
        clock) + the REAL measured service time of this flush -- the same
        accounting replay_trace uses, so the histogram p50/p95/p99 agree
        with ReplayResult on a replayed trace."""
        hist = obs.REGISTRY.histogram("serve_request_latency_seconds",
                                      "queue wait + measured service time")
        for q in reqs:
            wait = max(0.0, now - q.arrival) if now is not None else 0.0
            hist.observe(wait + dt, op=bkey[0], bits=bkey[1])


def _distinct_keys(reqs: List[BignumRequest]) -> int:
    """Distinct keys (rsa_*) or moduli (mod_exp) among a batch's lanes."""
    return len({(r.modulus, r.key) for r in reqs})


# ---------------------------------------------------------------------------
# one-at-a-time baseline
# ---------------------------------------------------------------------------

class NaiveServer:
    """One-request-at-a-time baseline: every call runs at batch 1 and
    its NATURAL width, jit-cached per (op, modulus, exponent width).  A
    shape-following server like this retraces whenever a new natural
    width or modulus shows up in traffic; ``warm()`` grants it the same
    finite-key head start the engine gets, which isolates the batching
    win from the retrace win in the benchmarks."""

    def __init__(self, *, backend: Optional[str] = None):
        self.backend = backend
        self.stats = EngineStats()
        self._fns: Dict[tuple, Callable] = {}

    def _fn(self, req: BignumRequest) -> Callable:
        if req.op not in OPS:
            raise ValueError(
                f"unknown serve op {req.op!r}; choose from {OPS}")
        if req.op == "mod_exp":
            key = (req.op, req.modulus, max(1, req.exponent.bit_length()))
        else:
            key = (req.op, req.key.n)
        if key in self._fns:
            return self._fns[key]
        stats = self.stats
        backend = self.backend
        if req.op == "mod_exp":
            ctx = api.mod_setup(req.modulus)

            def body(base, exp_bits, _ctx=ctx):
                stats.traces += 1
                return api.mod_exp(base, exp_bits, _ctx, backend=backend)
        elif req.op == "rsa_decrypt":
            k, crt = req.key, req.key.p != 0

            def body(base, _key=k, _crt=crt):
                stats.traces += 1
                return api.rsa_decrypt(base, _key, backend=backend,
                                       crt=_crt)
        else:
            f = api.rsa_sign if req.op == "rsa_sign" else api.rsa_verify
            k = req.key

            def body(base, _f=f, _key=k):
                stats.traces += 1
                return _f(base, _key, backend=backend)
        fn = jax.jit(body)
        self._fns[key] = fn
        stats.programs += 1
        return fn

    def serve(self, req: BignumRequest) -> np.ndarray:
        fn = self._fn(req)
        if req.op == "mod_exp":
            lw = -(-req.modulus.bit_length() // 32)
        else:
            lw = -(-req.key.bits // 32)
        base = np.zeros((1, lw), np.uint32)
        v = np.asarray(req.value, np.uint32).reshape(-1)
        base[0, : v.size] = v
        if req.op == "mod_exp":
            eb = np.asarray(api.exp_bits_msb(req.exponent))[None]
            out = fn(base, eb)
        else:
            out = fn(base)
        out = np.asarray(jax.block_until_ready(out))
        req.result = out[0, :lw]
        self.stats.served += 1
        self.stats.batches += 1
        return req.result

    def warm(self, op: str, *, modulus: Optional[int] = None,
             exponent: Optional[int] = None,
             key: Optional[api.RSAKey] = None) -> None:
        self.serve(BignumRequest(rid=-1, op=op,
                                 value=np.zeros(1, np.uint32),
                                 modulus=modulus, exponent=exponent,
                                 key=key))
        self.stats.served -= 1          # warm-ups don't count as traffic
        self.stats.batches -= 1


# ---------------------------------------------------------------------------
# trace replay (virtual arrival clock, real measured service times)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ReplayResult:
    n: int
    makespan_s: float
    ops_per_s: float
    p50_ms: float
    p99_ms: float
    mean_ms: float


def _summarize(reqs: List[BignumRequest]) -> ReplayResult:
    lats = np.array([r.latency for r in reqs]) * 1e3
    t0 = min(r.arrival for r in reqs)
    t1 = max(r.completion for r in reqs)
    makespan = max(t1 - t0, 1e-12)
    return ReplayResult(len(reqs), makespan, len(reqs) / makespan,
                        float(np.percentile(lats, 50)),
                        float(np.percentile(lats, 99)),
                        float(lats.mean()))


def replay_trace(engine: BignumEngine,
                 trace: List[BignumRequest]) -> ReplayResult:
    """Event-driven replay: arrivals advance a virtual clock; each
    engine call that completes requests is timed for real (the engine
    blocks on device results) and that wall time becomes the service
    time on the virtual clock.  The single device is a serial server:
    work triggered at virtual time t starts at max(t, server-free)."""
    trace = sorted(trace, key=lambda r: r.arrival)
    free = 0.0
    done: List[BignumRequest] = []
    i = 0
    while i < len(trace) or engine.pending():
        nxt = trace[i].arrival if i < len(trace) else float("inf")
        dl = engine.next_deadline()
        if dl is not None and dl <= nxt:
            start = max(dl, free)
            t0 = time.perf_counter()
            reqs = engine.flush_next_due(dl)
            dt = time.perf_counter() - t0
        else:
            r = trace[i]
            i += 1
            start = max(r.arrival, free)
            t0 = time.perf_counter()
            reqs = engine.submit(r, r.arrival)
            dt = time.perf_counter() - t0
        if reqs:
            free = start + dt
            for q in reqs:
                q.completion = free
            done += reqs
    return _summarize(done)


def replay_naive(server: NaiveServer,
                 trace: List[BignumRequest]) -> ReplayResult:
    """Same replay model for the one-at-a-time baseline: each request
    is served alone the moment the server frees up after its arrival
    (compile time, if the shape/modulus is new, lands in its service
    time -- that's the cost a shape-following server actually pays)."""
    trace = sorted(trace, key=lambda r: r.arrival)
    free = 0.0
    for r in trace:
        start = max(r.arrival, free)
        t0 = time.perf_counter()
        server.serve(r)
        dt = time.perf_counter() - t0
        r.completion = start + dt
        free = r.completion
    return _summarize(trace)


def poisson_trace(ops: List[dict], n: int, rate_per_s: float,
                  seed: int = 0) -> List[BignumRequest]:
    """n requests with exponential interarrivals at ``rate_per_s``,
    cycling through ``ops`` (dicts of BignumRequest kwargs minus
    rid/arrival) in round-robin so every replay sees the same op mix
    regardless of rate."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate_per_s, size=n)
    t = 0.0
    out = []
    for i in range(n):
        t += float(gaps[i])
        out.append(BignumRequest(rid=i, arrival=t, **ops[i % len(ops)]))
    return out
