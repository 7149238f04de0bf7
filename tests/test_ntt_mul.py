"""NTT/CRT huge-operand multiply subsystem (kernels/ntt_mul) vs Python-int
ground truth, plus the layers under it: the uint32-only wide-multiply /
Montgomery primitives, the twiddle tables, the forward transform against
an O(N^2) DFT oracle, the crt_combine kernel (Garner CRT recombination)
against Python ints and its jnp reference, and the core/mul.py dispatch
tier that routes huge operands here.

Oracle widths follow the CI fast-subset policy: 4096/8192-bit oracles run
on PRs, the >= 16384-bit grid (where a single interpret-mode launch still
takes seconds) is slow-marked.  Both CRT prime-set sizes (2 and 3) are
exercised at every tested width, at batch 1 and batch >= 8.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import repro.core.mul as M
from repro.core import limbs as L
from repro.kernels.ntt_mul import kernel as NK
from repro.kernels.ntt_mul import ops as NO
from repro.kernels.ntt_mul import ref as NREF

RNG = np.random.default_rng(11)
R = 1 << 32


# ---------------------------------------------------------------------------
# uint32-only arithmetic primitives.
# ---------------------------------------------------------------------------

def test_mul32_wide_exact():
    xs = RNG.integers(0, 1 << 32, 256, dtype=np.int64).astype(np.uint32)
    ys = RNG.integers(0, 1 << 32, 256, dtype=np.int64).astype(np.uint32)
    # adversarial corners: the cross-sum and low-word carries must fire
    edge = np.array([0, 1, 0xFFFF, 0x10000, 0xFFFFFFFF, 0xFFFF0000,
                     0x0000FFFF, 0x80000000], np.uint32)
    xs = np.concatenate([xs, edge, edge])
    ys = np.concatenate([ys, edge, edge[::-1]])
    hi, lo = NK.mul32_wide(jnp.asarray(xs), jnp.asarray(ys))
    got = (np.asarray(hi).astype(np.uint64) << np.uint64(32)) \
        | np.asarray(lo).astype(np.uint64)
    want = xs.astype(np.uint64) * ys.astype(np.uint64)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("p", NK.PRIMES)
def test_mont_mul_matches_python(p):
    pinv = (-pow(p, -1, R)) % R
    xs = RNG.integers(0, p, 512, dtype=np.int64)
    ys = RNG.integers(0, p, 512, dtype=np.int64)
    # corners: 0, 1, p-1 against each other and the random draw
    edge = np.array([0, 1, p - 1, p // 2, p // 2 + 1], np.int64)
    xs = np.concatenate([xs, edge, edge])
    ys = np.concatenate([ys, edge, edge[::-1]])
    got = np.asarray(NK.mont_mul(jnp.asarray(xs.astype(np.uint32)),
                                 jnp.asarray(ys.astype(np.uint32)), p, pinv))
    rinv = pow(R, -1, p)
    for x, y, g in zip(xs, ys, got):
        assert int(g) == int(x) * int(y) * rinv % p


@pytest.mark.parametrize("p", NK.PRIMES)
def test_mod_add_sub(p):
    xs = RNG.integers(0, p, 256, dtype=np.int64)
    ys = RNG.integers(0, p, 256, dtype=np.int64)
    a = jnp.asarray(xs.astype(np.uint32))
    b = jnp.asarray(ys.astype(np.uint32))
    np.testing.assert_array_equal(
        np.asarray(NK.add_mod(a, b, p)), (xs + ys) % p)
    np.testing.assert_array_equal(
        np.asarray(NK.sub_mod(a, b, p)), (xs - ys) % p)


# ---------------------------------------------------------------------------
# Twiddle tables + the transform itself (vs an O(N^2) Python-int DFT).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("p", NK.PRIMES)
def test_twiddle_tables_are_root_powers(p):
    n = 64
    wf, wi = NO.twiddle_tables(p, n)
    rinv = pow(R, -1, p)
    w = pow(NK.GENERATOR, (p - 1) // n, p)
    assert pow(w, n, p) == 1 and pow(w, n // 2, p) == p - 1
    for s in range(n.bit_length() - 1):
        ln = n >> (s + 1)
        wm = pow(w, n // (2 * ln), p)
        for j in range(ln):
            assert int(wf[s, j]) * rinv % p == pow(wm, j, p), (s, j)
        ln_i = 1 << s
        wmi = pow(pow(w, -1, p), n // (2 * ln_i), p)
        for j in range(ln_i):
            assert int(wi[s, j]) * rinv % p == pow(wmi, j, p), (s, j)


@pytest.mark.parametrize("p", NK.PRIMES)
def test_forward_dif_matches_dft_ref(p):
    n = 32
    pinv = (-pow(p, -1, R)) % R
    x = RNG.integers(0, p, n, dtype=np.int64).astype(np.uint32)
    wf, _ = NO.lane_twiddles(p, n)
    got = np.asarray(NK.ntt_forward(jnp.asarray(x)[None, :],
                                    jnp.asarray(wf), p, pinv))[0]
    np.testing.assert_array_equal(got, NREF.ntt_fwd_ref(x, p))


def test_forward_inverse_roundtrip():
    """inv(fwd(x)) == x.  A pure roundtrip skips the pointwise stage, so
    the scale constant is N^-1 * R (one R to cancel its own mont_mul),
    not the production N^-1 * R^2 (which additionally cancels the
    pointwise product's stray R^-1)."""
    p = NK.PRIMES[0]
    n = 128
    pinv = (-pow(p, -1, R)) % R
    x = RNG.integers(0, p, (4, n), dtype=np.int64).astype(np.uint32)
    wf, wi = (jnp.asarray(t) for t in NO.lane_twiddles(p, n))
    f = NK.ntt_forward(jnp.asarray(x), wf, p, pinv)
    back = np.asarray(NK.ntt_inverse(f, wi, p, pinv,
                                     pow(n, -1, p) * R % p))
    np.testing.assert_array_equal(back, x)


# ---------------------------------------------------------------------------
# The crt_combine kernel (Garner CRT recombination) vs Python ints.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nprimes", [2, 3])
def test_crt_combine_matches_python(nprimes):
    """Random coefficient vectors up to the worst-case bound: residues
    in, exact digit expansion out (one carry resolve)."""
    nd_out = 32
    prs = NK.PRIMES[:nprimes]
    bound = NO.coefficient_bound(nd_out)
    assert bound < np.prod([int(p) for p in prs], dtype=object)
    vals = [int(RNG.integers(0, 1 << 62)) * int(RNG.integers(0, 16)) % bound
            for _ in range(nd_out)]
    vals[0] = bound - 1                      # pin the extreme coefficient
    want = sum(v << (16 * j) for j, v in enumerate(vals))
    res = tuple(
        jnp.asarray(np.array([[v % p for v in vals]], np.uint32))
        for p in prs)
    got = np.asarray(NO.crt_combine(res, nd_out))[0]
    assert got.max() <= 0xFFFF
    assert L.limbs_to_int(got, 16) == want % (1 << (16 * nd_out))


def _crt_residues(coeffs, nprimes, width):
    """Rows of python-int coefficients -> per-prime (rows, width) residue
    arrays, zero beyond the coefficients (as an NTT's padded tail)."""
    return tuple(
        jnp.asarray(np.array([[v % p for v in row] + [0] * (width - len(row))
                              for row in coeffs], np.uint32))
        for p in NK.PRIMES[:nprimes])


def _check_crt(coeffs, nprimes, nd_out, width):
    got = np.asarray(NO.crt_combine(_crt_residues(coeffs, nprimes, width),
                                    nd_out))
    assert got.shape == (len(coeffs), nd_out)
    assert got.max() <= 0xFFFF
    for i, row in enumerate(coeffs):
        want = sum(v << (16 * j) for j, v in enumerate(row))
        assert L.limbs_to_int(got[i], 16) == want % (1 << (16 * nd_out)), i


@pytest.mark.parametrize("nprimes", [2, 3])
def test_crt_combine_worst_case_coefficients(nprimes):
    """Every coefficient of every column at coefficient_bound - 1: the
    largest Garner digits on every column at once, and the longest
    carry runs through the resolve."""
    nd_out = 256
    top = NO.coefficient_bound(nd_out) - 1
    _check_crt([[top] * nd_out] * 8, nprimes, nd_out, nd_out)


@pytest.mark.parametrize("nprimes", [2, 3])
def test_crt_combine_batch_not_a_tile_multiple(nprimes):
    nd_out, rows = 64, 13
    assert rows % NO._crt_tile(nd_out, rows) != 0   # a partial last block
    bound = NO.coefficient_bound(nd_out)
    coeffs = [[int(RNG.integers(0, 1 << 62)) % bound for _ in range(nd_out)]
              for _ in range(rows)]
    _check_crt(coeffs, nprimes, nd_out, 2 * nd_out)


@pytest.mark.parametrize("nprimes", [2, 3])
def test_crt_combine_width_not_a_lane_multiple(nprimes):
    """328 output digits out of 1024-wide residues: the kernel reads a
    384-column block and keeps 328 columns of it."""
    nd_out = 328
    bound = NO.coefficient_bound(nd_out)
    coeffs = [[int(RNG.integers(0, 1 << 62)) % bound for _ in range(nd_out)]
              for _ in range(8)]
    _check_crt(coeffs, nprimes, nd_out, 1024)


@pytest.mark.parametrize("nprimes", [2, 3])
def test_crt_combine_matches_jnp_ref(nprimes):
    """Uniform residues, CRT-consistent or not, equal the plain-jnp
    Garner recombination bit for bit."""
    nd_out, rows = 256, 16
    res = tuple(jnp.asarray(RNG.integers(0, p, (rows, 2 * nd_out),
                                         dtype=np.int64).astype(np.uint32))
                for p in NK.PRIMES[:nprimes])
    np.testing.assert_array_equal(np.asarray(NO.crt_combine(res, nd_out)),
                                  np.asarray(NREF.crt_combine_ref(res,
                                                                  nd_out)))


def test_resolve_nprimes_validation():
    with pytest.raises(ValueError, match="must be 2 or 3"):
        NO._resolve_nprimes(64, 4)
    with pytest.raises(ValueError, match="overflow the 2-prime"):
        NO._resolve_nprimes(1 << 25, 2)      # past the 2-prime bound
    assert NO._resolve_nprimes(1 << 20, 2) == 2
    assert NO._resolve_nprimes(4096, None) in (2, 3)   # config default


# ---------------------------------------------------------------------------
# End-to-end oracles (the acceptance grid).  4096/8192 fast; >= 16384 slow.
# ---------------------------------------------------------------------------

def _check_ntt_mul(nbits, batch, nprimes):
    m = nbits // 32
    xs = L.random_bigints(RNG, batch, nbits)
    ys = L.random_bigints(RNG, batch, nbits)
    prod = np.asarray(NO.ntt_mul_limbs32(
        jnp.asarray(L.ints_to_batch(xs, m)),
        jnp.asarray(L.ints_to_batch(ys, m)), nprimes=nprimes))
    assert prod.shape == (batch, 2 * m)
    for i, (x, y) in enumerate(zip(xs, ys)):
        assert L.limbs_to_int(prod[i]) == x * y, (nbits, batch, nprimes, i)


@pytest.mark.parametrize("nbits,batch,nprimes", [
    (4096, 8, 2), (4096, 8, 3), (4096, 1, 2),
    (8192, 8, 2), (8192, 1, 3),
])
def test_ntt_mul_vs_python_int(nbits, batch, nprimes):
    _check_ntt_mul(nbits, batch, nprimes)


@pytest.mark.slow
@pytest.mark.parametrize("nbits,batch,nprimes", [
    (16384, 8, 2), (16384, 8, 3),
    (65536, 8, 2), (65536, 8, 3), (65536, 1, 2),
])
def test_ntt_mul_vs_python_int_wide(nbits, batch, nprimes):
    _check_ntt_mul(nbits, batch, nprimes)


def test_ntt_mul_pathological():
    """All-max operands hit the worst-case CRT coefficient bound."""
    nbits = 4096
    m = nbits // 32
    pairs = L.pathological_pairs(nbits)
    a = jnp.asarray(L.ints_to_batch([q[0] for q in pairs], m))
    b = jnp.asarray(L.ints_to_batch([q[1] for q in pairs], m))
    prod = np.asarray(NO.ntt_mul_limbs32(a, b, nprimes=2))
    for i, (x, y) in enumerate(pairs):
        assert L.limbs_to_int(prod[i]) == x * y, i


def test_ntt_mul_odd_batch_padding():
    """Non-tile batch exercises the pad/trim path; jnp Karatsuba ref.
    Width stays small: the ref's eager Karatsuba trace is the cost."""
    nbits, batch = 1024, 5
    m = nbits // 32
    xs = L.random_bigints(RNG, batch, nbits)
    ys = L.random_bigints(RNG, batch, nbits)
    a, b = L.ints_to_batch(xs, m), L.ints_to_batch(ys, m)
    got = np.asarray(NO.ntt_mul_limbs32(jnp.asarray(a), jnp.asarray(b)))
    want = np.asarray(NREF.ntt_mul_limbs32_ref(a, b))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# Dispatch: the "ntt" tier in core/mul.select_method + mul_limbs32.
# ---------------------------------------------------------------------------

def test_select_method_ntt_tier():
    from repro.configs.dot_bignum import MUL_DISPATCH as cfg
    B = 512
    assert M.select_method(cfg.ntt_min_bits, batch=B) == "ntt"
    assert M.select_method(65536, batch=B) == "ntt"
    assert M.select_method(cfg.ntt_min_bits - 32, batch=B) == "karatsuba"
    # huge operands take the NTT kernel even below the kernel batch
    # threshold (its compile stays flat where jnp Karatsuba's explodes)
    assert M.select_method(cfg.small_batch_dot_max_bits + 32,
                           batch=1) == "ntt"
    assert M.select_method(cfg.small_batch_dot_max_bits, batch=1) == "dot"
    # prefer_mxu cannot reach past the Toeplitz range
    assert M.select_method(65536, batch=B, prefer_mxu=True) == "ntt"


def test_ntt_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_MUL_BACKEND", "ntt")
    assert M.select_method(256, batch=1) == "ntt"


def test_mul_limbs32_auto_routes_ntt_exact():
    nbits, batch = 8192, 8
    m = nbits // 32
    assert M.select_method(nbits, batch=batch) == "ntt"
    xs = L.random_bigints(RNG, batch, nbits)
    ys = L.random_bigints(RNG, batch, nbits)
    p = np.asarray(M.mul_limbs32(jnp.asarray(L.ints_to_batch(xs, m)),
                                 jnp.asarray(L.ints_to_batch(ys, m)),
                                 method="auto"))
    for i, (x, y) in enumerate(zip(xs, ys)):
        assert L.limbs_to_int(p[i]) == x * y, i


def test_mul_limbs32_ntt_leading_batch_dims():
    nbits = 8192
    m = nbits // 32
    xs = L.random_bigints(RNG, 8, nbits)
    ys = L.random_bigints(RNG, 8, nbits)
    a = L.ints_to_batch(xs, m).reshape(2, 4, m)
    b = L.ints_to_batch(ys, m).reshape(2, 4, m)
    p = np.asarray(M.mul_limbs32(a, b, method="ntt"))
    assert p.shape == (2, 4, 2 * m)
    flat = p.reshape(8, 2 * m)
    for i, (x, y) in enumerate(zip(xs, ys)):
        assert L.limbs_to_int(flat[i]) == x * y, i


def test_unknown_method_error_lists_methods():
    a = L.ints_to_batch([3], 4)
    with pytest.raises(ValueError) as e:
        M.mul_limbs32(a, a, method="bogus")
    msg = str(e.value)
    for name in M.MUL_METHODS:
        assert name in msg
    assert "REPRO_MUL_BACKEND" in msg


# ---------------------------------------------------------------------------
# The division subsystem rides the tier automatically via method="auto".
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_divmod_wide_rides_ntt_tier():
    """8192-bit divmod: every Newton multiply above 4096 bits dispatches
    to the NTT kernel (batch-1 regime) and the result stays exact."""
    from repro.core import div as DV
    nbits_a, nbits_b = 8192, 4224
    ma, mb = nbits_a // 32, nbits_b // 32
    xs = L.random_bigints(RNG, 2, nbits_a)
    ys = [y | 1 for y in L.random_bigints(RNG, 2, nbits_b)]
    q, r = DV.divmod_limbs32(jnp.asarray(L.ints_to_batch(xs, ma)),
                             jnp.asarray(L.ints_to_batch(ys, mb)))
    q, r = np.asarray(q), np.asarray(r)
    for i, (x, y) in enumerate(zip(xs, ys)):
        assert L.limbs_to_int(q[i]) == x // y, i
        assert L.limbs_to_int(r[i]) == x % y, i
