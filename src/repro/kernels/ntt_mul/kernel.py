"""Fused NTT multiply Pallas kernel: huge-operand multiplication as pure
lane-parallel butterflies (the limit case of the paper's restructuring).

Above the fused-Karatsuba range the jnp composition pays a quadratic-ish
price exactly where scale matters.  The number-theoretic transform is
the paper's thesis taken to its limit: EVERY butterfly of every stage is
an independent mul/add mod p over the batch x lane grid -- no carry
chains, no shared accumulators, nothing sequential but the log2(N) stage
order (van der Hoeven & Lecerf's "Modular SIMD arithmetic" route to
large-operand throughput).

One launch per CRT prime multiplies a (TB, N) batch tile end to end:

  forward DIF NTT(a), forward DIF NTT(b)   (natural -> bit-reversed)
  pointwise Montgomery product
  inverse DIT NTT                          (bit-reversed -> natural)

The DIF/DIT pairing means NO bit-reversal permutation ever materializes
-- the pointwise product is order-agnostic, so the reversed order lives
only between the transforms.  Twiddle factors are precomputed on the
host (ops.py) in Montgomery form and stay VMEM-resident for the whole
launch; the kernel reads stage s as a static row slice.  Butterfly
partners meet by lane rotation, not by reshaping into blocks.

Word-size modular arithmetic WITHOUT 64-bit integers: the TPU VPU (and
uint32-only Pallas) cannot widen a 32x32 product, so modmuls run as
Montgomery multiplication (R = 2**32) built from 16-bit half products --
the same lo/hi split the paper uses for simd_mul_lo/hi, applied to the
REDC step.  Primes are < 2**30, so every half-product sum stays in
uint32 (see the bound notes on ``mul32_wide``).  Values stay in the
NORMAL domain throughout: twiddles are stored as w*R mod p, so
``mont_mul(x, w*R) = x*w mod p`` -- only the pointwise product picks up
a stray R**-1, cancelled by folding R**2 into the inverse transform's
1/N scale constant.

CRT recombination of the per-prime residues runs in plain jnp (ops.py)
and funnels into ONE deferred-carry resolve via common/carry.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

U32 = jnp.uint32
R_BITS = 32                      # Montgomery radix R = 2**32

# NTT-friendly primes p = c * 2**k + 1 (ascending -- Garner's mixed-radix
# recombination in ops.py relies on p1 < p2 < p3 so residues never need a
# pre-reduction), all < 2**30 so Montgomery half-product sums fit uint32,
# all with primitive root 3 and 2-adic order >= 2**23 (transform lengths
# to 8M points; a 64K-bit operand needs only N = 2**13).
PRIMES = (167772161,             # 5   * 2**25 + 1
          469762049,             # 7   * 2**26 + 1
          998244353)             # 119 * 2**23 + 1
GENERATOR = 3

# Live (TB, N) uint32 arrays in the fused body: both operands, both
# transforms, the butterfly temps, and the ~8 half-product temps inside a
# Montgomery multiply (those are (TB, N/2)-sized; counted as halves).
LIVE_U32_ARRAYS = 16
MAX_TILE = 128


# ---------------------------------------------------------------------------
# uint32-only modular arithmetic (kernel-safe: branch-free, no uint64).
# ---------------------------------------------------------------------------

def mul32_wide(x, y):
    """Exact 64-bit product of uint32 arrays as a (hi, lo) uint32 pair.

    Schoolbook over 16-bit halves.  ``cross = lh + hl`` can wrap (for
    x, y < 2**31 it cannot, but REDC calls this with a full-range m), so
    the wrap is detected by the unsigned compare and re-injected at bit
    48 -- the standard carry-save emulation of a widening multiply.
    """
    x0 = x & np.uint32(0xFFFF)
    x1 = x >> np.uint32(16)
    y0 = y & np.uint32(0xFFFF)
    y1 = y >> np.uint32(16)
    ll = x0 * y0
    lh = x0 * y1
    hl = x1 * y0
    hh = x1 * y1
    cross = lh + hl                          # may wrap once
    cc = (cross < lh).astype(U32)            # carry out of the cross sum
    lo = ll + ((cross & np.uint32(0xFFFF)) << np.uint32(16))
    cl = (lo < ll).astype(U32)               # carry out of the low word
    hi = hh + (cross >> np.uint32(16)) + (cc << np.uint32(16)) + cl
    return hi, lo


def mont_mul(x, y, p: int, pinv: int):
    """x * y * R**-1 mod p for x, y in [0, p), p < 2**31 (R = 2**32).

    REDC: m = (x*y mod R) * (-p**-1) mod R; t = (x*y + m*p) / R < 2p;
    one branch-free conditional subtract canonicalizes.  The low words
    of x*y and m*p cancel mod R by construction, so their carry into the
    high word is exactly ``lo != 0``.
    """
    hi, lo = mul32_wide(x, y)
    m = lo * np.uint32(pinv)                 # wrapping product mod R
    mp_hi, _ = mul32_wide(m, np.uint32(p))
    t = hi + mp_hi + (lo != 0).astype(U32)
    return jnp.where(t >= np.uint32(p), t - np.uint32(p), t)


def add_mod(a, b, p: int):
    s = a + b                                # < 2p < 2**32
    return jnp.where(s >= np.uint32(p), s - np.uint32(p), s)


def sub_mod(a, b, p: int):
    d = a + (np.uint32(p) - b)
    return jnp.where(d >= np.uint32(p), d - np.uint32(p), d)


# ---------------------------------------------------------------------------
# Radix-2 stages (static Python loop -- log2(N) stages, every butterfly
# lane-parallel).  Each stage works on the whole (TB, N) array: a lane
# rotation by the half-block size brings every element's butterfly
# partner into its own lane, and a lane mask picks the top or bottom
# output -- no reshape, which Mosaic cannot lower for half-blocks
# narrower than a vreg.  Twiddle rows are Montgomery-domain, one (N,)
# row per stage (ops.lane_twiddles: row s holds w^(k mod half) at lane k).
# ---------------------------------------------------------------------------

def _partner(x, half: int, lo):
    """x[k + half] at lanes with bit ``half`` clear, x[k - half] where set."""
    n = x.shape[-1]
    return jnp.where(lo, jnp.roll(x, n - half, 1), jnp.roll(x, half, 1))


def _low_half(shape, half: int):
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return (lane & half) == 0


def ntt_forward(x, wf, p: int, pinv: int):
    """DIF forward transform, natural order in -> bit-reversed out.

    x: (TB, N); wf: (log2 N, N) lane twiddles.  Butterfly on the pair
    (u, v) = (x[j], x[j+half]): (u+v, (u-v) * w^j).
    """
    n = x.shape[-1]
    for s in range(n.bit_length() - 1):
        half = n >> (s + 1)
        lo = _low_half(x.shape, half)
        y = _partner(x, half, lo)
        x = jnp.where(lo, add_mod(x, y, p),
                      mont_mul(sub_mod(y, x, p), wf[s:s + 1, :], p, pinv))
    return x


def ntt_inverse(x, wi, p: int, pinv: int, scale: int):
    """DIT inverse transform, bit-reversed in -> natural out.

    Butterfly on (u, v) = (x[j], x[j+half]): (u + w^-j v, u - w^-j v);
    the final Montgomery scale constant is N**-1 * R**2 mod p, which
    both divides by N and cancels the R**-1 the pointwise product
    introduced.
    """
    n = x.shape[-1]
    for s in range(n.bit_length() - 1):
        half = 1 << s
        lo = _low_half(x.shape, half)
        t = mont_mul(x, wi[s:s + 1, :], p, pinv)   # w^-j v at the v lanes
        x = jnp.where(lo, add_mod(x, _partner(t, half, lo), p),
                      sub_mod(_partner(x, half, lo), t, p))
    return mont_mul(x, jnp.full((), np.uint32(scale), U32), p, pinv)


def make_ntt_mul_kernel(p: int, pinv: int, scale: int):
    """Fused body: NTT(a), NTT(b), pointwise, inverse -- one launch."""

    def ntt_mul_kernel(a_ref, b_ref, wf_ref, wi_ref, out_ref):
        wf = wf_ref[...]
        wi = wi_ref[...]
        fa = ntt_forward(a_ref[...], wf, p, pinv)
        fb = ntt_forward(b_ref[...], wf, p, pinv)
        c = mont_mul(fa, fb, p, pinv)        # carries one stray R**-1
        out_ref[...] = ntt_inverse(c, wi, p, pinv, scale)

    return ntt_mul_kernel


def make_ntt_mul_prepared_kernel(p: int, pinv: int, scale: int):
    """Fused body with operand b already transformed: NTT(a), pointwise
    against the cached forward residue row, inverse -- one launch that
    skips one of the two forward transforms (~1/3 of transform work).

    ``fb_ref`` is a (1, N) NORMAL-domain forward transform of the fixed
    operand (ops.prepared_operand); the pointwise Montgomery product
    broadcasts it over the batch tile and picks up the same stray R**-1
    as the two-transform kernel, cancelled by the inverse scale.
    """

    def ntt_mul_prepared_kernel(a_ref, fb_ref, wf_ref, wi_ref, out_ref):
        wf = wf_ref[...]
        wi = wi_ref[...]
        fa = ntt_forward(a_ref[...], wf, p, pinv)
        c = mont_mul(fa, fb_ref[...], p, pinv)   # (TB,N)x(1,N) broadcast
        out_ref[...] = ntt_inverse(c, wi, p, pinv, scale)

    return ntt_mul_prepared_kernel


def _derived_constants(n: int, p: int):
    assert n & (n - 1) == 0, "transform length must be a power of two"
    order = (p - 1) & -(p - 1)
    assert n <= order, f"prime {p} has 2-adic order {order} < N={n}"
    pinv = (-pow(p, -1, 1 << R_BITS)) % (1 << R_BITS)
    scale = pow(n, -1, p) * pow(2, 2 * R_BITS, p) % p
    return pinv, scale


@functools.lru_cache(maxsize=64)
def make_prepared_call(batch_tile: int, n: int, grid: int, p: int,
                       interpret: bool):
    """pallas_call for one prime with a prepared operand: (batch, N) a,
    (1, N) forward residue of b, twiddles -> residues."""
    pinv, scale = _derived_constants(n, p)
    stages = n.bit_length() - 1
    return pl.pallas_call(
        make_ntt_mul_prepared_kernel(p, pinv, scale),
        name="ntt_mul_prepared",
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((batch_tile, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((stages, n), lambda i: (0, 0)),
            pl.BlockSpec((stages, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((batch_tile, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((grid * batch_tile, n), U32),
        interpret=interpret,
    )


@functools.lru_cache(maxsize=64)
def make_call(batch_tile: int, n: int, grid: int, p: int, interpret: bool):
    """pallas_call for one prime: (batch, N) x2 + twiddles -> residues.

    p, and the constants derived from it here, are trace-time Python
    ints (scalar closures are kernel-safe); the twiddle tables are
    runtime inputs mapped whole into every program (VMEM-resident).
    """
    pinv, scale = _derived_constants(n, p)
    stages = n.bit_length() - 1
    return pl.pallas_call(
        make_ntt_mul_kernel(p, pinv, scale),
        # no name=: it would rename the HLO instruction (_call) that the
        # benchmark's kernel metric keys on; kernel_name stays the
        # function's name, ntt_mul_kernel
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((batch_tile, n), lambda i: (i, 0)),
            pl.BlockSpec((batch_tile, n), lambda i: (i, 0)),
            pl.BlockSpec((stages, n), lambda i: (0, 0)),
            pl.BlockSpec((stages, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((batch_tile, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((grid * batch_tile, n), U32),
        interpret=interpret,
    )
