"""Fused Montgomery kernel (interpret mode on CPU) vs the Python-int
oracle, plus backend-dispatch agreement and RSA round-trips through the
pallas path.  The oracle (kernels/dot_modmul/ref.py) is independent of
all jnp code, so a kernel bug and a core/modular.py bug cannot cancel.
"""
import functools

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import limbs as L
from repro.core import modular as M
from repro.core import rsa as R
from repro.kernels.dot_modmul import ops, ref

RNG = np.random.default_rng(13)


def _odd_modulus(nbits):
    return L.random_bigints(RNG, 1, nbits)[0] | (1 << (nbits - 1)) | 1


def _digit_batch(ints, m):
    return np.stack([L.int_to_limbs(v, m, 16) for v in ints])


@pytest.mark.parametrize("nbits", [256, 512, 1024])
def test_mont_mul_kernel_vs_oracle(nbits):
    n = _odd_modulus(nbits)
    ctx = M.mont_setup(n, nbits)
    xs = [v % n for v in L.random_bigints(RNG, 9, nbits)]
    ys = [v % n for v in L.random_bigints(RNG, 9, nbits)]
    out = np.asarray(ops.dot_mont_mul(
        _digit_batch(xs, ctx.m), _digit_batch(ys, ctx.m), ctx))
    want = ref.mont_mul_ref(_digit_batch(xs, ctx.m),
                            _digit_batch(ys, ctx.m), n)
    np.testing.assert_array_equal(out, want)


@pytest.mark.parametrize("batch", [1, 7, 300])
def test_mont_mul_kernel_padding_tiles(batch):
    nbits = 128
    n = _odd_modulus(nbits)
    ctx = M.mont_setup(n, nbits)
    xs = [v % n for v in L.random_bigints(RNG, batch, nbits)]
    ys = [v % n for v in L.random_bigints(RNG, batch, nbits)]
    out = np.asarray(ops.dot_mont_mul(
        _digit_batch(xs, ctx.m), _digit_batch(ys, ctx.m), ctx))
    want = ref.mont_mul_ref(_digit_batch(xs, ctx.m),
                            _digit_batch(ys, ctx.m), n)
    np.testing.assert_array_equal(out, want)


def test_mont_mul_kernel_edge_operands():
    """0, 1, n-1 exercise the conditional-subtract boundary."""
    nbits = 192
    n = _odd_modulus(nbits)
    ctx = M.mont_setup(n, nbits)
    xs = [0, 1, n - 1, n - 1, 1, n // 2]
    ys = [0, 1, n - 1, 1, n - 1, 2]
    out = np.asarray(ops.dot_mont_mul(
        _digit_batch(xs, ctx.m), _digit_batch(ys, ctx.m), ctx))
    want = ref.mont_mul_ref(_digit_batch(xs, ctx.m),
                            _digit_batch(ys, ctx.m), n)
    np.testing.assert_array_equal(out, want)


@pytest.mark.parametrize("nbits,ebits", [(256, 64), (512, 32), (1024, 16)])
def test_mod_exp_kernel_vs_oracle(nbits, ebits):
    n = _odd_modulus(nbits)
    ctx = M.mont_setup(n, nbits)
    e = L.random_bigints(RNG, 1, ebits)[0] | 1
    xs = [v % n for v in L.random_bigints(RNG, 4, nbits)]
    out = np.asarray(ops.dot_mod_exp(
        _digit_batch(xs, ctx.m), jnp.asarray(M.exp_bits_msb(e)), ctx))
    want = ref.mod_exp_ref(_digit_batch(xs, ctx.m), e, n)
    np.testing.assert_array_equal(out, want)


def test_mod_exp_kernel_per_lane_exponents():
    nbits = 128
    n = _odd_modulus(nbits)
    ctx = M.mont_setup(n, nbits)
    xs = [v % n for v in L.random_bigints(RNG, 6, nbits)]
    es = [v | 1 for v in L.random_bigints(RNG, 6, 32)]
    eb = jnp.asarray(np.stack([M.exp_bits_msb(e, 32) for e in es]))
    out = np.asarray(ops.dot_mod_exp(_digit_batch(xs, ctx.m), eb, ctx))
    for i, (x, e) in enumerate(zip(xs, es)):
        assert L.limbs_to_int(out[i], 16) == pow(x, e, n), i


@functools.lru_cache(maxsize=None)
def _lane_keys(nbits, count):
    return [R.generate_key(bits=nbits, seed=500 + k) for k in range(count)]


@pytest.mark.parametrize("backend", ["pallas", "jnp", "reference"])
def test_mod_exp_per_lane_keys_vs_pow(backend):
    """8 lanes under 8 different 512-bit keys in one batch: lane i is
    m_i^d_i mod n_i (the Montgomery ladder with per-lane moduli)."""
    keys = _lane_keys(512, 8)
    lanes = R.key_lanes(keys)
    ms = [v % k.n for v, k in zip(L.random_bigints(RNG, 8, 512), keys)]
    out = np.asarray(M.mod_exp(jnp.asarray(_digit_batch(ms, 32)),
                               lanes.d_bits, lanes.ctx, backend=backend))
    for i, (m, k) in enumerate(zip(ms, keys)):
        assert L.limbs_to_int(out[i], 16) == pow(m, k.d, k.n), i


def test_mont_mul_kernel_per_lane_moduli():
    ns = [_odd_modulus(192) for _ in range(9)]
    ctx = M.mont_lanes(ns, 192)
    xs = [v % n for v, n in zip(L.random_bigints(RNG, 9, 192), ns)]
    ys = [v % n for v, n in zip(L.random_bigints(RNG, 9, 192), ns)]
    a, b = _digit_batch(xs, ctx.m), _digit_batch(ys, ctx.m)
    want = ref.mont_mul_ref(a, b, ns)
    for backend in ("pallas", "jnp", "reference"):
        out = np.asarray(M.mont_mul(a, b, ctx, backend=backend))
        np.testing.assert_array_equal(out, want, backend)


@pytest.mark.parametrize("backend", ["barrett", "barrett_fused"])
def test_barrett_refuses_per_lane_moduli(backend):
    ns = [_odd_modulus(128) for _ in range(4)]
    ctx = M.mont_lanes(ns, 128)
    x = jnp.asarray(_digit_batch([3, 5, 7, 11], ctx.m))
    with pytest.raises(ValueError, match="per-lane"):
        M.mod_exp(x, jnp.asarray(M.exp_bits_msb(65537)), ctx,
                  backend=backend)
    with pytest.raises(ValueError, match="per-lane"):
        M.mod_mul(x, x, ctx, backend=backend)


def test_rsa_sign_with_a_key_per_lane():
    """api.rsa_sign takes keys one a lane (KeyLanes), and verify gives
    the messages back under the same keys."""
    from repro import api
    keys = _lane_keys(256, 5)
    lanes = api.key_lanes(keys)
    ms = [v % k.n for v, k in zip(L.random_bigints(RNG, 5, 256), keys)]
    sigs = api.rsa_sign(api.to_limbs(ms, 256), lanes, backend="pallas")
    assert api.from_limbs(sigs) == [pow(m, k.d, k.n)
                                    for m, k in zip(ms, keys)]
    back = api.rsa_verify(sigs, lanes, backend="pallas")
    assert api.from_limbs(back) == ms
    with pytest.raises(ValueError, match="one width"):
        api.key_lanes([keys[0], R.generate_key(bits=128, seed=1)])


def test_backend_dispatch_agreement():
    """reference / jnp / pallas produce identical digits via one API."""
    nbits = 128
    n = _odd_modulus(nbits)
    ctx = M.mont_setup(n, nbits)
    xs = [v % n for v in L.random_bigints(RNG, 5, nbits)]
    ys = [v % n for v in L.random_bigints(RNG, 5, nbits)]
    a = jnp.asarray(_digit_batch(xs, ctx.m))
    b = jnp.asarray(_digit_batch(ys, ctx.m))
    outs = {be: np.asarray(M.mod_mul(a, b, ctx, backend=be))
            for be in M.BACKENDS}
    for be in M.BACKENDS:
        np.testing.assert_array_equal(outs[be], outs["reference"], be)
    e = 65537
    eb = jnp.asarray(M.exp_bits_msb(e))
    outs = {be: np.asarray(M.mod_exp(a, eb, ctx, backend=be))
            for be in M.BACKENDS}
    for be in M.BACKENDS:
        np.testing.assert_array_equal(outs[be], outs["reference"], be)


def test_default_backend_setter():
    assert M.get_default_backend() == "jnp"
    with pytest.raises(ValueError):
        M.set_default_backend("nope")
    M.set_default_backend("pallas")
    try:
        assert M.get_default_backend() == "pallas"
    finally:
        M.set_default_backend("jnp")


def test_explicit_backend_ignores_default():
    """backend="jnp" must not leak through to the module default (the
    internal to_mont/from_mont calls once did, crashing under jit when
    the default was "reference")."""
    import jax
    nbits = 128
    n = _odd_modulus(nbits)
    ctx = M.mont_setup(n, nbits)
    xs = [v % n for v in L.random_bigints(RNG, 3, nbits)]
    a = jnp.asarray(_digit_batch(xs, ctx.m))
    eb = jnp.asarray(M.exp_bits_msb(65537))
    M.set_default_backend("reference")
    try:
        out = np.asarray(jax.jit(
            lambda x: M.mod_exp(x, eb, ctx, backend="jnp"))(a))
    finally:
        M.set_default_backend("jnp")
    for i, x in enumerate(xs):
        assert L.limbs_to_int(out[i], 16) == pow(x, 65537, n), i


def test_rsa_sign_verify_roundtrip_pallas():
    """Full modexp round-trip through core/rsa.py on the pallas backend."""
    key = R.generate_key(bits=256, seed=7)
    msgs = [R.digest_int(f"pmsg{i}".encode(), key.bits) for i in range(4)]
    md = R.messages_to_digits(msgs, key)
    sigs = R.sign(md, key, backend="pallas")
    back = np.asarray(R.verify(sigs, key, backend="pallas"))
    for i, m in enumerate(msgs):
        assert L.limbs_to_int(back[i], 16) == m % key.n
    # oracle: python pow, and cross-backend identical signatures
    s0 = L.limbs_to_int(np.asarray(sigs)[0], 16)
    assert s0 == pow(msgs[0] % key.n, key.d, key.n)
    sigs_jnp = np.asarray(R.sign(md, key, backend="jnp"))
    np.testing.assert_array_equal(np.asarray(sigs), sigs_jnp)
