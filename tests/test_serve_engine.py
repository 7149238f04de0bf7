"""serve/bignum_engine: shape bucketing, the no-retrace contract,
flush policy (batch-full vs deadline), padding, and batched == one-at-
a-time determinism.  Everything runs at tiny widths on the jnp backend
so the compiles stay cheap; the replay-policy tests stub out the
device work entirely and drive the virtual clock by hand."""
import random

import numpy as np
import pytest

from repro import api
from repro.configs.dot_bignum import SERVE, ServeConfig, quantize_bits
from repro.serve import bignum_engine as BE

PY = random.Random(99)


def _odd(bits):
    return PY.getrandbits(bits) | 1 | (1 << (bits - 1))


def _mod_exp_req(rid, n, e=None):
    e = e if e is not None else PY.getrandbits(24) | 1
    base = PY.randrange(2, n)
    return BE.BignumRequest(rid=rid, op="mod_exp",
                            value=api.to_limbs(base, n.bit_length()),
                            modulus=n, exponent=e)


def _oracle(r):
    return pow(int(api.from_limbs(np.asarray(r.value))), r.exponent,
               r.modulus)


SMALL = ServeConfig(bucket_bits=(96, 160), exp_bucket_bits=(16, 32, 64),
                    slots=4, max_wait_s=0.02)


# ---------------------------------------------------------------------------
# quantization
# ---------------------------------------------------------------------------

def test_quantize_bits():
    assert quantize_bits(1, (256, 512)) == 256
    assert quantize_bits(256, (256, 512)) == 256
    assert quantize_bits(257, (256, 512)) == 512
    assert quantize_bits(300, SERVE.bucket_bits) == 512
    with pytest.raises(ValueError, match="choose from"):
        quantize_bits(600, (256, 512))
    with pytest.raises(ValueError):
        quantize_bits(0, (256,))


def test_bucket_key_quantizes_widths():
    eng = BE.BignumEngine(SMALL)
    n80, n90, n150 = _odd(80), _odd(90), _odd(150)
    k80 = eng.bucket_key(_mod_exp_req(0, n80, e=3))
    k90 = eng.bucket_key(_mod_exp_req(1, n90, e=3))
    k150 = eng.bucket_key(_mod_exp_req(2, n150, e=3))
    # same modulus bucket iff same (width tier, exp tier, modulus)
    assert k80[:3] == k90[:3] == ("mod_exp", 96, 16)
    assert k80 != k90                    # modulus is part of the key
    assert k150[1] == 160
    key = api.generate_key(96, seed=5)
    krsa = eng.bucket_key(BE.BignumRequest(
        rid=3, op="rsa_sign", value=np.zeros(3, np.uint32), key=key))
    assert krsa == ("rsa_sign", 96, None, key.n)   # natural width


def test_unknown_op_message():
    eng = BE.BignumEngine(SMALL)
    with pytest.raises(ValueError) as e:
        eng.bucket_key(BE.BignumRequest(rid=0, op="frobnicate",
                                        value=np.zeros(1, np.uint32)))
    msg = str(e.value)
    assert "frobnicate" in msg
    for op in BE.OPS:
        assert op in msg


# ---------------------------------------------------------------------------
# replay policy on a stubbed engine (no device work, hand-driven clock)
# ---------------------------------------------------------------------------

def _stub(engine):
    lw = max(engine.cfg.bucket_bits) // 32
    engine._execute = lambda bkey, reqs: np.zeros(
        (engine.cfg.slots, lw), np.uint32)
    return engine


def test_full_flush_on_slots_submissions():
    eng = _stub(BE.BignumEngine(SMALL))
    n = _odd(80)
    done = []
    for i in range(SMALL.slots):
        done += eng.submit(_mod_exp_req(i, n, e=5), now=0.001 * i)
    assert [r.rid for r in done] == list(range(SMALL.slots))
    assert eng.stats.flush_full == 1 and eng.stats.flush_deadline == 0
    assert eng.stats.padded_lanes == 0 and eng.pending() == 0


def test_deadline_flush_pads_partial_batch():
    eng = _stub(BE.BignumEngine(SMALL))
    n = _odd(80)
    assert eng.submit(_mod_exp_req(0, n, e=5), now=1.0) == []
    assert eng.submit(_mod_exp_req(1, n, e=5), now=1.005) == []
    # deadline comes from the OLDEST request in the bucket
    assert eng.next_deadline() == pytest.approx(1.0 + SMALL.max_wait_s)
    assert eng.flush_next_due(1.0 + SMALL.max_wait_s / 2) == []
    done = eng.flush_next_due(1.0 + SMALL.max_wait_s)
    assert [r.rid for r in done] == [0, 1]
    assert eng.stats.flush_deadline == 1
    assert eng.stats.padded_lanes == SMALL.slots - 2
    assert eng.next_deadline() is None


def test_replay_deadline_vs_full_regimes():
    n = _odd(80)
    tmpl = [dict(op="mod_exp", value=api.to_limbs(2, 80), modulus=n,
                 exponent=7)]
    # sparse arrivals (mean gap 10x max_wait): every flush is a deadline
    eng = _stub(BE.BignumEngine(SMALL))
    res = BE.replay_trace(eng, BE.poisson_trace(
        tmpl, 8, rate_per_s=1.0 / (10 * SMALL.max_wait_s), seed=2))
    assert res.n == 8 and eng.stats.flush_full == 0
    assert eng.stats.flush_deadline > 0
    # every lone request waits out its deadline before being served
    assert res.p50_ms >= SMALL.max_wait_s * 1e3
    # dense arrivals (mean gap max_wait/100): batches fill
    eng2 = _stub(BE.BignumEngine(SMALL))
    res2 = BE.replay_trace(eng2, BE.poisson_trace(
        tmpl, 16, rate_per_s=100.0 / SMALL.max_wait_s, seed=3))
    assert res2.n == 16 and eng2.stats.flush_full == 16 // SMALL.slots


# ---------------------------------------------------------------------------
# real compute: no-retrace contract, correctness, determinism
# ---------------------------------------------------------------------------

def test_mixed_shape_trace_zero_retraces_after_warm():
    eng = BE.BignumEngine(SMALL, backend="jnp")
    n1, n2 = _odd(80), _odd(150)      # distinct width tiers
    e = 0x10001
    eng.warm("mod_exp", modulus=n1, exponent=e)
    eng.warm("mod_exp", modulus=n2, exponent=e)
    assert eng.stats.programs == 2
    after_warm = eng.stats.traces
    reqs = [_mod_exp_req(i, n1 if i % 2 == 0 else n2, e=e)
            for i in range(10)]
    tmpl = [dict(op=r.op, value=r.value, modulus=r.modulus,
                 exponent=r.exponent) for r in reqs]
    res = BE.replay_trace(eng, BE.poisson_trace(tmpl, 10, 500.0, seed=4))
    assert res.n == 10
    assert eng.stats.traces == after_warm, (
        f"engine retraced on a warmed mixed-shape trace: {eng.stats}")
    # and a second identical trace stays flat too
    BE.replay_trace(eng, BE.poisson_trace(tmpl, 10, 500.0, seed=5))
    assert eng.stats.traces == after_warm


def test_batched_equals_one_at_a_time_and_oracle():
    n = _odd(90)
    reqs = [_mod_exp_req(i, n) for i in range(6)]
    eng = BE.BignumEngine(SMALL, backend="jnp")
    done = []
    for r in reqs:
        done += eng.submit(r, now=0.0)
    while eng.pending():
        done += eng.drain_one()
    assert sorted(r.rid for r in done) == list(range(6))
    naive = BE.NaiveServer(backend="jnp")
    for r in reqs:
        want = _oracle(r)
        assert int(api.from_limbs(r.result)) == want, r.rid
        single = BE.BignumRequest(rid=r.rid, op=r.op, value=r.value,
                                  modulus=r.modulus, exponent=r.exponent)
        naive.serve(single)
        assert int(api.from_limbs(single.result)) == want, r.rid
    # 6 reqs over 4 slots: one full flush + one padded drain
    assert eng.stats.flush_full == 1 and eng.stats.padded_lanes == 2


def test_rsa_ops_through_engine():
    key = api.generate_key(128, seed=11)
    msg = api.digest_int(b"engine", key.bits) % key.n
    cfg = ServeConfig(bucket_bits=(128,), exp_bucket_bits=(256,),
                      slots=2, max_wait_s=0.01)
    eng = BE.BignumEngine(cfg, backend="jnp")
    sig_req = BE.BignumRequest(rid=0, op="rsa_sign",
                               value=api.to_limbs(msg, key.bits), key=key)
    ver_req = BE.BignumRequest(
        rid=1, op="rsa_verify",
        value=api.to_limbs(pow(msg, key.d, key.n), key.bits), key=key)
    dec_req = BE.BignumRequest(
        rid=2, op="rsa_decrypt",
        value=api.to_limbs(pow(msg, key.e, key.n), key.bits), key=key)
    done = []
    for r in (sig_req, ver_req, dec_req):
        done += eng.submit(r, now=0.0)
    while eng.pending():
        done += eng.drain_one()
    assert len(done) == 3
    assert int(api.from_limbs(sig_req.result)) == pow(msg, key.d, key.n)
    assert int(api.from_limbs(ver_req.result)) == msg
    assert int(api.from_limbs(dec_req.result)) == msg
    # three ops -> three distinct programs, all padded singleton batches
    assert eng.stats.programs == 3 and eng.stats.padded_lanes == 3


# ---------------------------------------------------------------------------
# fault tolerance: lifecycle, shedding, retry, degradation, selfcheck
# ---------------------------------------------------------------------------

@pytest.fixture(autouse=True)
def _resilience_clean():
    from repro import config
    from repro.resilience import inject
    from repro.resilience.breaker import BREAKER
    # the fault tests exercise degradation, which is opt-in
    config.set_overrides({"kernel_fallback": True})
    inject.clear()
    BREAKER.reset()
    yield
    inject.clear()
    BREAKER.reset()
    config.set_overrides({"selfcheck": None, "kernel_fallback": None})


def test_warm_is_idempotent_per_bucket():
    eng = BE.BignumEngine(SMALL, backend="jnp")
    n = _odd(80)
    eng.warm("mod_exp", modulus=n, exponent=0x10001)
    traces = eng.stats.traces
    eng.warm("mod_exp", modulus=n, exponent=0x10001)   # no-op: no retrace
    assert eng.stats.traces == traces
    assert eng.stats.programs == 1


def test_close_lifecycle():
    eng = _stub(BE.BignumEngine(SMALL))
    n = _odd(80)
    eng.submit(_mod_exp_req(0, n, e=5), now=0.0)
    done = eng.close()                     # drains the pending request
    assert [r.rid for r in done] == [0] and not done[0].shed
    assert eng.close() == []               # idempotent
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit(_mod_exp_req(1, n, e=5), now=0.0)
    with pytest.raises(RuntimeError, match="closed"):
        eng.warm("mod_exp", modulus=n, exponent=5)


def test_close_without_drain_sheds():
    eng = _stub(BE.BignumEngine(SMALL))
    n = _odd(80)
    eng.submit(_mod_exp_req(0, n, e=5), now=0.0)
    done = eng.close(drain=False)
    assert len(done) == 1 and done[0].shed and done[0].result is None
    assert eng.stats.shed == 1 and eng.pending() == 0


def test_submit_sheds_on_queue_bound():
    cfg = ServeConfig(bucket_bits=SMALL.bucket_bits,
                      exp_bucket_bits=SMALL.exp_bucket_bits,
                      slots=4, max_wait_s=10.0, max_queue=2)
    eng = _stub(BE.BignumEngine(cfg))
    n = _odd(80)
    assert eng.submit(_mod_exp_req(0, n, e=5), now=0.0) == []
    assert eng.submit(_mod_exp_req(1, n, e=5), now=0.0) == []
    out = eng.submit(_mod_exp_req(2, n, e=5), now=0.0)
    assert len(out) == 1 and out[0].shed and out[0].result is None
    assert eng.stats.shed == 1 and eng.pending() == 2


def test_submit_sheds_when_deadline_slips():
    eng = _stub(BE.BignumEngine(SMALL))
    n = _odd(80)
    eng.submit(_mod_exp_req(0, n, e=5), now=0.0)
    # arrival far past the oldest deadline + max_wait: overloaded
    out = eng.submit(_mod_exp_req(1, n, e=5), now=10 * SMALL.max_wait_s)
    assert len(out) == 1 and out[0].shed


def _flaky_stub(engine, fail_times, exc=None):
    """_execute fails the first ``fail_times`` calls, then succeeds."""
    lw = max(engine.cfg.bucket_bits) // 32
    calls = {"n": 0}

    def execute(bkey, reqs):
        calls["n"] += 1
        if calls["n"] <= fail_times:
            raise exc or RuntimeError("transient flush failure")
        return np.zeros((engine.cfg.slots, lw), np.uint32)

    engine._execute = execute
    return calls


def test_flush_retries_then_succeeds():
    eng = BE.BignumEngine(SMALL)
    calls = _flaky_stub(eng, fail_times=2)        # max_retries=2 absorbs
    n = _odd(80)
    eng.submit(_mod_exp_req(0, n, e=5), now=0.0)
    done = eng.drain_one()
    assert [r.rid for r in done] == [0]
    assert calls["n"] == 3 and eng.stats.retries == 2
    assert eng.stats.degraded == 0


def test_flush_degrades_bucket_after_retries():
    eng = BE.BignumEngine(SMALL, backend=None)
    calls = _flaky_stub(eng, fail_times=3)        # retries exhausted once
    n = _odd(80)
    req = _mod_exp_req(0, n, e=5)
    eng.submit(req, now=0.0)
    done = eng.drain_one()
    assert [r.rid for r in done] == [0]
    bkey = eng.bucket_key(req)
    assert eng._degraded[bkey] == "jnp"           # auto -> jnp
    assert eng.stats.degraded == 1 and eng.stats.retries == 2
    assert calls["n"] == 4                        # 3 failures + 1 at jnp


def test_degradation_ladder_reaches_reference():
    eng = BE.BignumEngine(SMALL, backend="jnp")
    n = _odd(80)
    req = _mod_exp_req(0, n, e=5)
    bkey = eng.bucket_key(req)
    assert eng._next_tier(bkey) == "reference"    # jnp degrades straight
    eng._degraded[bkey] = "reference"
    assert eng._next_tier(bkey) is None           # floor: nothing below
    # the reference tier serves exactly (host python-int, no jit)
    eng.submit(req, now=0.0)
    done = eng.drain_one()
    assert int(api.from_limbs(done[0].result)) == _oracle(req)
    assert eng.stats.traces == 0                  # never touched jax


def test_warm_partial_failure_degrades_not_fatal():
    eng = BE.BignumEngine(SMALL, backend="jnp")
    n = _odd(80)
    calls = {"n": 0}
    real = eng._execute

    def flaky(bkey, reqs):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("warm-time compile blew up")
        return real(bkey, reqs)

    eng._execute = flaky
    eng.warm("mod_exp", modulus=n, exponent=0x10001)   # degraded, not fatal
    bkey = eng.bucket_key(BE.BignumRequest(
        rid=-1, op="mod_exp", value=np.zeros(1, np.uint32), modulus=n,
        exponent=0x10001))
    assert eng._degraded[bkey] == "reference"     # jnp -> reference
    assert eng.stats.degraded == 1
    req = _mod_exp_req(0, n, e=0x10001)
    eng.submit(req, now=0.0)
    done = eng.drain_one()
    assert int(api.from_limbs(done[0].result)) == _oracle(req)


def test_strict_default_warm_and_flush_raise():
    from repro import config
    config.set_overrides({"kernel_fallback": None})   # the default: strict
    eng = BE.BignumEngine(SMALL, backend="jnp")
    n = _odd(80)

    def broken(bkey, reqs):
        raise RuntimeError("kernel failed to lower")

    eng._execute = broken
    with pytest.raises(RuntimeError, match="lower"):
        eng.warm("mod_exp", modulus=n, exponent=0x10001)
    eng.submit(_mod_exp_req(0, n, e=0x10001), now=0.0)
    with pytest.raises(RuntimeError, match="lower"):
        eng.drain_one()
    assert eng.stats.degraded == 0 and not eng._degraded
    assert eng.pending() == 1                     # the batch is kept


def test_deadline_miss_counter():
    eng = _stub(BE.BignumEngine(SMALL))
    n = _odd(80)
    r0 = _mod_exp_req(0, n, e=5)
    r0.sla_s = 1e-9                               # impossible SLA
    r1 = _mod_exp_req(1, n, e=5)
    r1.sla_s = 1e9                                # unmissable SLA
    eng.submit(r0, now=0.0)
    eng.submit(r1, now=0.0)
    eng.drain_one()
    assert eng.stats.deadline_misses == 1


def test_corrupt_injection_caught_and_repaired():
    from repro import config
    from repro.resilience import inject
    config.set_overrides({"selfcheck": "warn"})
    inject.install("corrupt", "serve/flush", seed=3)
    eng = BE.BignumEngine(SMALL, backend="jnp")
    n = _odd(80)
    reqs = [_mod_exp_req(i, n, e=0x10001) for i in range(SMALL.slots)]
    done = []
    with pytest.warns(Warning, match="selfcheck"):
        for r in reqs:
            done += eng.submit(r, now=0.0)
    assert len(done) == SMALL.slots
    n_corrupt = sum(1 for e in inject.log() if e["kind"] == "corrupt")
    assert n_corrupt == 1
    assert eng.stats.selfcheck_failures == 1
    for r in reqs:                                # repaired: all exact
        assert int(api.from_limbs(r.result)) == _oracle(r)
