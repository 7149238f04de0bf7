"""resilience/: breaker state machine (fake clock), deterministic
fault injection, guarded tiered execution, and residue self-checking.
Everything here is host-side -- no kernels compile -- so the state
machines are tested exactly, not statistically."""
import warnings

import numpy as np
import pytest

from repro import api, config
from repro.obs import metrics as _metrics
from repro.resilience import guard, inject, selfcheck
from repro.resilience.breaker import BREAKER, CircuitBreaker, shape_bucket


@pytest.fixture(autouse=True)
def _clean():
    # these tests exercise degradation, which is opt-in
    config.set_overrides({"kernel_fallback": True})
    inject.clear()
    BREAKER.reset()
    yield
    inject.clear()
    BREAKER.reset()
    config.set_overrides({"selfcheck": None})
    config.set_overrides({"kernel_fallback": None})


# ---------------------------------------------------------------------------
# breaker
# ---------------------------------------------------------------------------

def test_shape_bucket_powers_of_two():
    assert shape_bucket(1) == 32
    assert shape_bucket(32) == 32
    assert shape_bucket(33) == 64
    assert shape_bucket(1024) == 1024
    assert shape_bucket(1040) == 2048


def test_breaker_state_machine_fake_clock():
    t = [0.0]
    br = CircuitBreaker(cooldown_s=10.0, clock=lambda: t[0])
    assert br.state("mul", 256, "pallas") == "closed"
    assert br.allow("mul", 256, "pallas")
    br.record_failure("mul", 256, "pallas")
    assert br.state("mul", 256, "pallas") == "open"
    assert not br.allow("mul", 256, "pallas")
    # other shapes/backends unaffected
    assert br.allow("mul", 4096, "pallas")
    assert br.allow("mul", 256, "jnp")
    # cooldown expires -> half_open, exactly ONE probe allowed
    t[0] = 10.0
    assert br.state("mul", 256, "pallas") == "half_open"
    assert br.allow("mul", 256, "pallas")        # the probe
    assert not br.allow("mul", 256, "pallas")    # everyone else blocked
    br.record_failure("mul", 256, "pallas")      # probe failed: re-open
    assert br.state("mul", 256, "pallas") == "open"
    assert not br.allow("mul", 256, "pallas")
    t[0] = 20.0
    assert br.allow("mul", 256, "pallas")
    br.record_success("mul", 256, "pallas")      # probe passed: close
    assert br.state("mul", 256, "pallas") == "closed"
    assert br.allow("mul", 256, "pallas")


def test_breaker_force_open_and_snapshot():
    t = [0.0]
    br = CircuitBreaker(cooldown_s=5.0, clock=lambda: t[0])
    br.force_open(op="modexp", backend="pallas")
    assert not br.allow("modexp", 256, "pallas")
    assert br.state("modexp", 1024, "pallas") == "open"
    assert br.allow("modexp", 256, "jnp")        # pattern is keyed
    assert br.allow("mul", 256, "pallas")
    br.record_failure("mul", 512, "jnp")
    snap = br.snapshot()
    assert snap["forced"] == [{"op": "modexp", "backend": "pallas"}]
    assert snap["keys"]["mul/512/jnp"]["state"] == "open"
    assert snap["keys"]["mul/512/jnp"]["retry_in_s"] == pytest.approx(5.0)
    br.clear_forced()
    assert br.allow("modexp", 256, "pallas")


# ---------------------------------------------------------------------------
# injection
# ---------------------------------------------------------------------------

def test_inject_every_and_count_cadence():
    inject.install("compile_fail", "mul/pallas", every=2, count=2)
    fired = 0
    for _ in range(10):
        try:
            inject.fire("mul/pallas")
        except inject.InjectedFault:
            fired += 1
    assert fired == 2                        # calls 2 and 4, capped at 2
    assert [e["seq"] for e in inject.log()] == [1, 2]
    inject.fire("mul/jnp")                   # site mismatch: no-op


def test_inject_corrupt_deterministic():
    block = np.arange(12, dtype=np.uint32).reshape(4, 3)
    inject.install("corrupt", "serve/flush", seed=7)
    out1 = inject.corrupt("serve/flush/mod_exp", block.copy(), 2)
    inject.clear()
    inject.install("corrupt", "serve/flush", seed=7)
    out2 = inject.corrupt("serve/flush/mod_exp", block.copy(), 2)
    assert np.array_equal(out1, out2)        # same seed => same flip
    diff = np.nonzero(out1 != block)
    assert len(diff[0]) == 1                 # exactly one limb touched
    assert diff[0][0] < 2                    # only REAL lanes corrupted
    e = inject.log()[0]
    assert (e["lane"], e["limb"]) == (diff[0][0], diff[1][0])
    delta = int(out1[diff][0]) ^ int(block[diff][0])
    assert delta == 1 << e["bit"]            # single-bit flip


# ---------------------------------------------------------------------------
# guard
# ---------------------------------------------------------------------------

def _fallback_count(**labels):
    return _metrics.REGISTRY.counter(guard.METRIC).total(**labels)


def test_guard_falls_through_and_quarantines():
    calls = []

    def bad():
        calls.append("pallas")
        raise RuntimeError("Mosaic lowering failed")

    def good():
        calls.append("jnp")
        return 42

    t0 = _fallback_count(op="t_op")
    out = guard.run("t_op", 256, [("pallas", bad), ("jnp", good)])
    assert out == 42 and calls == ["pallas", "jnp"]
    assert _fallback_count(op="t_op", backend="pallas",
                           reason="lowering") - 0 == 1
    # breaker opened: next run skips the failing tier outright
    out = guard.run("t_op", 256, [("pallas", bad), ("jnp", good)])
    assert out == 42 and calls == ["pallas", "jnp", "jnp"]
    assert _fallback_count(op="t_op", reason="quarantined") == 1
    assert _fallback_count(op="t_op") - t0 == 2


def test_guard_final_tier_never_skipped_and_raises():
    def bad():
        raise RuntimeError("boom")

    BREAKER.record_failure("t_final", shape_bucket(256), "jnp")
    # final tier runs even with its breaker key open...
    assert guard.run("t_final", 256, [("jnp", lambda: 7)]) == 7
    # ...and its exception propagates (nothing left to fall back to)
    with pytest.raises(RuntimeError, match="boom"):
        guard.run("t_final", 256, [("pallas", bad), ("jnp", bad)])


def test_guard_strict_mode():
    def bad():
        raise RuntimeError("boom")

    config.set_overrides({"kernel_fallback": False})
    with pytest.raises(RuntimeError, match="boom"):
        guard.run("t_strict", 256, [("pallas", bad), ("jnp", lambda: 1)])
    # quarantine skipping still applies in strict mode
    assert guard.run("t_strict", 256,
                     [("pallas", bad), ("jnp", lambda: 1)]) == 1
    config.set_overrides({"kernel_fallback": None})


def test_kernel_fallback_strict_by_default():
    def bad():
        raise RuntimeError("boom")

    config.set_overrides({"kernel_fallback": None})
    assert not guard.fallback_enabled()
    with pytest.raises(RuntimeError, match="boom"):
        guard.run("t_default", 256, [("pallas", bad), ("jnp", lambda: 1)])
    assert _fallback_count(op="t_default", reason="RuntimeError") == 1


def test_guard_injected_fault_classified():
    inject.install("compile_fail", "t_inj/pallas")
    out = guard.run("t_inj", 512, [("pallas", lambda: 0),
                                   ("jnp", lambda: 9)])
    assert out == 9
    assert _fallback_count(op="t_inj", reason="injected") == 1
    assert len(inject.log()) == 1


def test_classify_reasons():
    assert guard.classify(inject.InjectedFault("x")) == "injected"
    assert guard.classify(RuntimeError("RESOURCE_EXHAUSTED: vmem")) == "oom"
    assert guard.classify(NotImplementedError("no lowering")) == "lowering"
    assert guard.classify(RuntimeError("compilation failure")) == "compile"
    assert guard.classify(KeyError("k")) == "KeyError"


# ---------------------------------------------------------------------------
# selfcheck
# ---------------------------------------------------------------------------

def test_fold_matches_int_mod_p():
    rng = np.random.default_rng(3)
    batch = rng.integers(0, 1 << 32, size=(8, 9), dtype=np.uint32)
    folds = selfcheck.fold_limbs(batch)
    for row, f in zip(batch, folds):
        assert int(f) == api.from_limbs(row) % selfcheck.P


def test_check_mul_catches_bit_flip():
    config.set_overrides({"selfcheck": "raise"})
    a = api.to_limbs([3, 5, (1 << 90) - 7], 96)
    b = api.to_limbs([7, 11, (1 << 80) + 9], 96)
    out = np.asarray(api.to_limbs(
        [ints_a * ints_b for ints_a, ints_b in
         zip(api.from_limbs(a), api.from_limbs(b))], 192))
    selfcheck.check_mul(a, b, out)           # exact product passes
    bad = out.copy()
    bad[1, 2] ^= np.uint32(1 << 13)
    with pytest.raises(selfcheck.SelfCheckError, match="1 mul lane"):
        selfcheck.check_mul(a, b, bad)
    assert _metrics.REGISTRY.counter(selfcheck.METRIC).total(op="mul") >= 1
    config.set_overrides({"selfcheck": "warn"})
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        selfcheck.check_mul(a, b, bad)
    assert any(issubclass(x.category, selfcheck.SelfCheckWarning)
               for x in w)


def test_check_divmod_identity():
    config.set_overrides({"selfcheck": "raise"})
    ints_a = [12345678901234567890, 999]
    ints_b = [97, 1000]
    a, b = api.to_limbs(ints_a, 96), api.to_limbs(ints_b, 96)
    q = api.to_limbs([x // y for x, y in zip(ints_a, ints_b)], 96)
    r = api.to_limbs([x % y for x, y in zip(ints_a, ints_b)], 96)
    selfcheck.check_divmod(a, b, q, r)
    bad = np.asarray(q).copy()
    bad[0, 0] ^= np.uint32(1)
    with pytest.raises(selfcheck.SelfCheckError):
        selfcheck.check_divmod(a, b, bad, r)


def test_verify_and_repair_lanes():
    key = api.generate_key(96, seed=21)
    msg = 0xABCDEF % key.n
    sig = pow(msg, key.d, key.n)
    assert selfcheck.verify_lane("rsa_sign", msg, sig, key=key)
    assert not selfcheck.verify_lane("rsa_sign", msg, sig ^ 1, key=key)
    assert selfcheck.repair_lane("rsa_sign", msg, key=key) == sig
    n, e = 1000003, 65537
    assert selfcheck.verify_lane("mod_exp", 5, pow(5, e, n),
                                 modulus=n, exponent=e)
    assert selfcheck.repair_lane("mod_exp", 5, modulus=n,
                                 exponent=e) == pow(5, e, n)
    with pytest.raises(ValueError, match="unknown op"):
        selfcheck.verify_lane("nope", 1, 1)


def test_selfcheck_disabled_is_noop():
    assert not selfcheck.enabled()
    a = api.to_limbs([3], 96)
    bad = np.asarray(api.to_limbs([999], 192))   # wrong on purpose
    selfcheck.check_mul(a, a, bad)               # no policy -> no check


# ---------------------------------------------------------------------------
# configure() knobs
# ---------------------------------------------------------------------------

def test_configure_selfcheck_and_kernel_fallback():
    with api.configure(selfcheck="warn", kernel_fallback=False):
        assert selfcheck.policy() == "warn"
        assert not guard.fallback_enabled()
    assert selfcheck.policy() is None
    assert guard.fallback_enabled()          # the fixture's opt-in is back
    with pytest.raises(ValueError, match="selfcheck"):
        api.configure(selfcheck="explode")
    with pytest.raises(ValueError, match="kernel_fallback"):
        api.configure(kernel_fallback="yes")
