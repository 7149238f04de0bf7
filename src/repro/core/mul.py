"""DoT multiplication (paper Algorithm 2) and baselines, adapted to TPU.

The paper's "vertical and crosswise" (VnC) organization exposes all m**2
partial products as independent work; on AVX-512 this feeds both IFMA ports.
On TPU we map the same structure two ways:

  * VPU path (``dot_mul``):  digits are radix 2**16 held in uint32 --- the
    TPU-native analogue of IFMA's 52-in-64 unsaturated radix.  A digit
    product fits *exactly* in uint32, so ``simd_mul_lo/hi`` (Alg. 2 lines
    16-17) become a single uint32 multiply plus mask/shift.  Column
    alignment (Phase 3) is a static skew-reshape; column reduction
    (Phase 4) is a vector sum; Phase 5's carry pass is a deferred-carry
    while-loop that converges in ~2 passes for random inputs (the
    multiplicative twin of DoT-add's Phase 4 rarity argument).

  * MXU path (``dot_mul_mxu``): the column sums ARE a convolution of the
    digit sequences, and a convolution is a (banded Toeplitz) matmul.  With
    radix 2**7 digits in int8 and int32 accumulation this runs on the MXU
    systolic array --- a genuinely TPU-native realization of the paper's
    insight (the MXU's 128x128 systolic grid replaces the two IFMA ports;
    every partial product is an independent MAC cell).  This is the
    beyond-paper optimization evaluated in EXPERIMENTS.md.

  * ``mul_schoolbook`` reproduces Gueron & Krasnov's shared-accumulator
    dependency structure (scan over b_j with a read-modify-write
    accumulator) as the baseline of Table 4.

  * ``karatsuba`` recurses with DoT as the base case, mirroring the DoTMP
    integration (paper sec 3.3): faster base-case multiply plus faster
    add/sub accelerate the whole recursion.

Digit conventions: little-endian, last axis; uint32 storage with digits
< 2**digit_bits ("normalized") unless a function documents a lazy range.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common.vnc import skew as _skew

U32 = jnp.uint32
I32 = jnp.int32

DIGIT_BITS = 16
DIGIT_MASK = jnp.uint32((1 << DIGIT_BITS) - 1)

MXU_DIGIT_BITS = 7


# ---------------------------------------------------------------------------
# Radix conversion (on-device twin of limbs.repack_np) --- the paper's
# "radix conversion" phase (Tables 1 and 3).
# ---------------------------------------------------------------------------

def split_digits(limbs: jax.Array, to_bits: int) -> jax.Array:
    """(..., m) uint32 limbs -> (..., m_to) uint32 digits < 2**to_bits."""
    assert 1 <= to_bits <= 32
    m_from = limbs.shape[-1]
    total = 32 * m_from
    m_to = -(-total // to_bits)
    j = np.arange(m_to)
    lo_bit = to_bits * j
    src = lo_bit // 32
    off = lo_bit % 32
    need2 = (off + to_bits > 32) & (src + 1 < m_from)
    src2 = np.minimum(src + 1, m_from - 1)
    sh2 = np.where(off > 0, 32 - off, 0).astype(np.uint32)

    with jax.named_scope("radix_split"):
        limbs = jnp.asarray(limbs, U32)
        v1 = limbs[..., src] >> jnp.asarray(off, U32)
        v2 = jnp.where(jnp.asarray(need2),
                       limbs[..., src2] << jnp.asarray(sh2, U32),
                       jnp.uint32(0))
        mask = jnp.uint32((1 << to_bits) - 1)
        return (v1 | v2) & mask


def join_digits(digits: jax.Array, from_bits: int, m_out: int) -> jax.Array:
    """(..., n) normalized digits < 2**from_bits -> (..., m_out) uint32 limbs.

    Limb i gathers the digits overlapping bit range [32i, 32(i+1)); each
    contributes via a static shift (slot k enumerates the at-most
    ceil(32/from_bits)+1 overlapping digits).
    """
    assert 1 <= from_bits <= 32
    with jax.named_scope("radix_join"):
        n = digits.shape[-1]
        digits = jnp.asarray(digits, U32)
        i = np.arange(m_out)
        max_slots = -(-32 // from_bits) + 1
        acc = jnp.zeros(digits.shape[:-1] + (m_out,), U32)
        for k in range(max_slots):
            d = 32 * i // from_bits + k        # digit feeding limb i, slot k
            sh = from_bits * d - 32 * i        # digit d's bit offset in limb i
            valid = (d < n) & (sh < 32)        # sh >= -from_bits always
            d_c = np.minimum(d, n - 1)
            vals = digits[..., d_c]
            left = np.clip(sh, 0, 31).astype(np.uint32)
            right = np.clip(-sh, 0, 31).astype(np.uint32)
            contrib = jnp.where(jnp.asarray(sh >= 0),
                                vals << jnp.asarray(left, U32),
                                vals >> jnp.asarray(right, U32))
            contrib = jnp.where(jnp.asarray(valid), contrib, jnp.uint32(0))
            acc = acc | contrib
        return acc


# ---------------------------------------------------------------------------
# Phase 5: carry normalization of column sums.
# ---------------------------------------------------------------------------

def normalize_digits(cols: jax.Array, digit_bits: int = DIGIT_BITS) -> jax.Array:
    """Deferred-carry normalization (DoT-style): repeat the O(1)-depth
    vector pass ``c <- (c & mask) + shift_up(c >> bits)`` until no digit
    exceeds the radix.  Random inputs converge in <= 2-3 passes; a
    pathological all-max chain degrades gracefully to O(m) passes, exactly
    mirroring DoT-add's common/rare split.  Total value is invariant and the
    top digit provably never overflows when the array is wide enough to hold
    the result (see DESIGN.md "Phase-5 invariant").
    """
    mask = jnp.uint32((1 << digit_bits) - 1)
    bits = jnp.uint32(digit_bits)

    def cond(c):
        return jnp.any(c > mask)

    def body(c):
        carry = c >> bits
        low = c & mask
        shifted = jnp.concatenate(
            [jnp.zeros(c.shape[:-1] + (1,), U32), carry[..., :-1]], axis=-1)
        return low + shifted

    return jax.lax.while_loop(cond, body, jnp.asarray(cols, U32))


def normalize_digits_scan(cols: jax.Array,
                          digit_bits: int = DIGIT_BITS) -> jax.Array:
    """Sequential Phase-5 pass (paper Alg. 2 lines 38-41), for baselines."""
    mask = jnp.uint32((1 << digit_bits) - 1)
    bits = jnp.uint32(digit_bits)

    def step(carry, col):
        t = col + carry
        return t >> bits, t & mask

    cols_t = jnp.moveaxis(jnp.asarray(cols, U32), -1, 0)
    carry0 = jnp.zeros(cols.shape[:-1], U32)
    _, out_t = jax.lax.scan(step, carry0, cols_t)
    return jnp.moveaxis(out_t, 0, -1)


# ---------------------------------------------------------------------------
# The skew trick (Phase 3's column alignment as a static reshape) is
# ``_skew``, shared with the kernel layer: kernels/common/vnc.skew.
# out[..., i, i+j] = mat[..., i, j]; anti-diagonal sums become column sums.
# ---------------------------------------------------------------------------
# DoT multiplication (Algorithm 2) --- VPU path, radix 2**16.
# ---------------------------------------------------------------------------

def dot_mul(a: jax.Array, b: jax.Array, digit_bits: int = DIGIT_BITS,
            normalize: str = "dot") -> jax.Array:
    """(..., m) x (..., m) normalized digits -> (..., 2m) normalized digits.

    Phase 1 (gather)      : implicit --- the broadcasted outer product
                            enumerates every (i, j) pair.
    Phase 2 (products)    : one uint32 multiply; lo/hi split replaces
                            vpmadd52lo/hi.  All m**2 products independent.
    Phase 3 (align)       : skew-reshape puts product (i, j) in column i+j
                            (hi parts in column i+j+1).
    Phase 4 (reduce)      : vector sum over the (independent) row axis.
    Phase 5 (carry pass)  : deferred-carry normalization.
    """
    assert digit_bits <= 16, "digit products must fit in uint32"
    a = jnp.asarray(a, U32)
    b = jnp.asarray(b, U32)
    m = a.shape[-1]
    assert b.shape[-1] == m

    prod = a[..., :, None] * b[..., None, :]          # (..., m, m) exact
    lo = prod & DIGIT_MASK if digit_bits == 16 else prod & jnp.uint32((1 << digit_bits) - 1)
    hi = prod >> jnp.uint32(digit_bits)

    lo_cols = _skew(lo).sum(axis=-2)                   # (..., 2m-1)
    hi_cols = _skew(hi).sum(axis=-2)

    zeros1 = jnp.zeros(a.shape[:-1] + (1,), U32)
    cols = jnp.concatenate([lo_cols, zeros1], axis=-1)         # (..., 2m)
    cols = cols + jnp.concatenate([zeros1, hi_cols], axis=-1)  # hi -> c+1

    if normalize == "dot":
        return normalize_digits(cols, digit_bits)
    return normalize_digits_scan(cols, digit_bits)


# ---------------------------------------------------------------------------
# MXU path: column sums as an int8 x int8 -> int32 Toeplitz matmul.
# ---------------------------------------------------------------------------

def dot_mul_mxu(a: jax.Array, b: jax.Array,
                digit_bits: int = MXU_DIGIT_BITS) -> jax.Array:
    """(..., m) digits < 2**7 (any int dtype) -> (..., 2m) normalized digits.

    cols[c] = sum_{i+j=c} a_i * b_j  ==  a (1 x m) @ T (m x 2m-1),
    T[i, i+j] = b_j.  int8 operands with int32 accumulation target the MXU.
    Column sums < m * 127**2, exact in int32 for m < 2**17.
    """
    m = a.shape[-1]
    a8 = jnp.asarray(a, jnp.int8)
    b8 = jnp.asarray(b, jnp.int8)
    bt = jnp.broadcast_to(b8[..., None, :], b8.shape[:-1] + (m, m))
    T = _skew(bt)                                      # (..., m, 2m-1)
    cols = jnp.einsum("...i,...ic->...c", a8, T,
                      preferred_element_type=I32)      # MXU: int8 -> int32
    zeros1 = jnp.zeros(cols.shape[:-1] + (1,), I32)
    cols = jnp.concatenate([cols, zeros1], axis=-1).astype(U32)
    return normalize_digits(cols, digit_bits)


# ---------------------------------------------------------------------------
# Baseline: schoolbook with a shared accumulator (Gueron & Krasnov's RAW
# chain, Table 4).  scan(acc <- acc + row_j) serializes on the accumulator.
# ---------------------------------------------------------------------------

def mul_schoolbook(a: jax.Array, b: jax.Array,
                   digit_bits: int = DIGIT_BITS) -> jax.Array:
    a = jnp.asarray(a, U32)
    b = jnp.asarray(b, U32)
    m = a.shape[-1]
    mask = jnp.uint32((1 << digit_bits) - 1)
    bits = jnp.uint32(digit_bits)

    a_pad = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, m)])    # (..., 2m)

    def step(carry, bj):
        acc, j = carry
        prod = a_pad * bj[..., None]          # digits j..j+m-1 of row j
        lo = prod & mask
        hi = prod >> bits
        hi = jnp.concatenate(
            [jnp.zeros(hi.shape[:-1] + (1,), U32), hi[..., :-1]], axis=-1)
        row = lo + hi                          # lazy, < 2**17
        row = jnp.roll(row, j, axis=-1)        # align to column j
        return (acc + row, j + 1), None

    b_t = jnp.moveaxis(b, -1, 0)
    acc0 = jnp.zeros(a_pad.shape, U32)
    (acc, _), _ = jax.lax.scan(step, (acc0, jnp.uint32(0)), b_t)
    # paper: "store & normalize" is the sequential drain.
    return normalize_digits_scan(acc, digit_bits)


# ---------------------------------------------------------------------------
# Digit-domain helpers for Karatsuba (lazy uint32 digit arithmetic).
# ---------------------------------------------------------------------------

def _pad_to(x: jax.Array, n: int) -> jax.Array:
    m = x.shape[-1]
    if m == n:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, n - m)])


def digit_sub_abs(x: jax.Array, y: jax.Array,
                  digit_bits: int = DIGIT_BITS) -> Tuple[jax.Array, jax.Array]:
    """|x - y| on equal-length normalized digit arrays, plus sign.

    Returns (|x - y| normalized, neg) with neg = 1 where x < y.
    Uses radix-complement addition: x - y + B**n = x + ~y + 1; the carry out
    of the top digit is 1 iff x >= y.
    """
    n = x.shape[-1]
    mask = jnp.uint32((1 << digit_bits) - 1)
    comp = (mask - y) & mask
    s = x + comp                                # lazy, < 2**17
    one = jnp.zeros(x.shape[:-1] + (n + 1,), U32).at[..., 0].set(1)
    s = _pad_to(s, n + 1) + one
    s = normalize_digits(s, digit_bits)
    ge = s[..., -1]                             # carry out: 1 iff x >= y
    d_pos = s[..., :-1]                         # x - y      (valid when ge)
    # if x < y: result held x - y + B**n; |x - y| = B**n - that = complement+1
    comp_d = (mask - d_pos) & mask
    d_neg = normalize_digits(
        _pad_to(comp_d, n + 1) + one, digit_bits)[..., :-1]
    neg = (ge == 0).astype(U32)
    out = jnp.where(neg[..., None] == 1, d_neg, d_pos)
    return out, neg


def mul_karatsuba(a: jax.Array, b: jax.Array, threshold: int = 16,
                  digit_bits: int = DIGIT_BITS,
                  base=dot_mul) -> jax.Array:
    """Karatsuba over normalized digit arrays with a DoT base case.

    Mirrors paper Algorithm 4 + the DoTMP integration: the recursion's
    add/sub work runs in the lazy digit domain (deferred carries), and the
    base case is DoT multiplication.  Returns (..., 2m) normalized digits.
    """
    a = jnp.asarray(a, U32)
    b = jnp.asarray(b, U32)
    m = a.shape[-1]
    assert b.shape[-1] == m
    if m <= threshold:
        return base(a, b) if base is not dot_mul else dot_mul(a, b, digit_bits)
    if m % 2:
        a, b = _pad_to(a, m + 1), _pad_to(b, m + 1)
        return mul_karatsuba(a, b, threshold, digit_bits, base)[..., : 2 * m]
    k = m // 2
    a_l, a_h = a[..., :k], a[..., k:]
    b_l, b_h = b[..., :k], b[..., k:]

    p0 = mul_karatsuba(a_l, b_l, threshold, digit_bits, base)   # (..., 2k)
    p1 = mul_karatsuba(a_h, b_h, threshold, digit_bits, base)   # (..., 2k)
    da, sa = digit_sub_abs(a_h, a_l, digit_bits)
    db, sb = digit_sub_abs(b_h, b_l, digit_bits)
    pd = mul_karatsuba(da, db, threshold, digit_bits, base)     # (..., 2k)

    # middle = p1 + p0 -/+ pd  (sign = sa XOR sb); always >= 0.
    neg = (sa ^ sb).astype(U32)
    s01 = p0 + p1                                               # lazy < 2**17
    n = 2 * k
    mask = jnp.uint32((1 << digit_bits) - 1)
    # mid_minus = s01 - pd via radix complement: s01 + ~pd + 1 = mid + B**n.
    # 0 <= mid < 2*B**n, so after normalization the top digit is 1 + the
    # overflow digit of mid; subtracting 1 never borrows.
    comp = (mask - pd) & mask
    tot = _pad_to(s01 + comp, n + 1).at[..., 0].add(1)
    tot = normalize_digits(tot, digit_bits)
    mid_minus = tot.at[..., -1].set(tot[..., -1] - 1)
    mid_plus = normalize_digits(_pad_to(s01 + pd, n + 1), digit_bits)
    mid = jnp.where(neg[..., None] == 1, mid_plus, mid_minus)   # (..., 2k+1)

    out = jnp.zeros(a.shape[:-1] + (2 * m,), U32)
    out = out.at[..., : 2 * k].add(p0)
    out = out.at[..., k: k + 2 * k + 1].add(mid)
    out = out.at[..., 2 * k:].add(p1)
    return normalize_digits(out, digit_bits)


# ---------------------------------------------------------------------------
# 32-bit limb entry points (the GMP/OpenSSL-facing API of sec 3.3: accept
# the saturated radix used by the host library, convert, multiply, convert
# back --- the "radix conversion packing at entry / unpacking at exit").
#
# The unified pipeline front door: ``method="auto"`` routes through
# ``select_method`` (size-based dispatch over the jnp compositions AND the
# Pallas kernel family -- VPU-VnC, MXU Toeplitz, fused Karatsuba).
# ---------------------------------------------------------------------------

MUL_METHODS = ("dot", "mxu", "schoolbook", "karatsuba",
               "pallas", "pallas_mxu", "pallas_kara", "ntt")


def select_method(nbits: int, batch: int = 1,
                  prefer_mxu: bool = False) -> str:
    """Size-based multiply dispatch (see configs/dot_bignum.MUL_DISPATCH).

    * tiny operands: the jnp VnC composition ("dot"); a kernel launch
      costs more than it saves,
    * up to one base case (512 bits): the single-launch Pallas VnC
      kernel ("pallas"),
    * 512..4096 bits: the fused Karatsuba kernel ("pallas_kara"),
    * beyond the fused kernel's overflow analysis: the jnp Karatsuba
      composition ("karatsuba"),
    * huge operands (>= ``cfg.ntt_min_bits``): the fused NTT/CRT kernel
      family ("ntt") -- O(n log n) butterflies, one launch per CRT prime
      (kernels/ntt_mul).

    ``prefer_mxu`` selects the int8 Toeplitz kernel where its range
    allows (worth it when the MXU would otherwise sit idle).  A
    ``repro.api.configure(mul_method=...)`` override wins over
    everything (ops knob for A/B experiments without code changes); the
    REPRO_MUL_BACKEND env var is its deprecated alias.

    Batch awareness: the kernels tile the BATCH axis -- that is where
    the carry machinery amortizes.  Below ``cfg.kernel_min_batch``
    independent operations a launch cannot pay for itself (and on CPU
    its interpret-mode compile dwarfs the work), so small batches take
    the jnp compositions while the quadratic VnC outer product stays
    small.  The NTT tier is the exception: above the small-batch dot
    range it runs even at batch 1, because its trace is O(log n) stages
    (a batch-1 launch compiles in seconds, where the jnp Karatsuba
    composition's compile takes minutes past 4096 bits) and its
    O(n log n) work beats the composition outright.  The division
    subsystem's batch-1 paths (base conversion, the pi workload) live
    in this regime -- their huge-width multiplies ride the NTT tier
    automatically.
    """
    from repro import config as _rc
    from repro.configs.dot_bignum import MUL_DISPATCH as cfg
    from repro.obs import trace as _trace

    override = _rc.resolve("mul_method", MUL_METHODS, "multiply method")
    if override:
        choice, rule, detail = override, "override", {}
    elif batch < cfg.kernel_min_batch:
        if nbits <= cfg.small_batch_dot_max_bits:
            choice, rule = "dot", "small_batch_dot_max_bits"
            detail = {"threshold": cfg.small_batch_dot_max_bits}
        else:
            choice, rule = "ntt", "small_batch_ntt"
            detail = {"threshold": cfg.small_batch_dot_max_bits}
    elif prefer_mxu and nbits <= cfg.mxu_max_bits:
        choice, rule = "pallas_mxu", "prefer_mxu"
        detail = {"threshold": cfg.mxu_max_bits}
    elif nbits <= cfg.jnp_max_bits:
        choice, rule = "dot", "jnp_max_bits"
        detail = {"threshold": cfg.jnp_max_bits}
    elif nbits <= cfg.vnc_max_bits:
        choice, rule = "pallas", "vnc_max_bits"
        detail = {"threshold": cfg.vnc_max_bits}
    elif nbits <= cfg.fused_kara_max_bits:
        choice, rule = "pallas_kara", "fused_kara_max_bits"
        detail = {"threshold": cfg.fused_kara_max_bits}
    elif nbits < cfg.ntt_min_bits:
        choice, rule = "karatsuba", "below_ntt_min_bits"
        detail = {"threshold": cfg.ntt_min_bits}
    else:
        choice, rule = "ntt", "ntt_min_bits"
        detail = {"threshold": cfg.ntt_min_bits}
    _trace.emit("mul", nbits, batch, choice, rule, **detail)
    return choice


def _flatten_leading(x: jax.Array):
    return x.reshape((-1, x.shape[-1])), x.shape[:-1]


def mul_limbs32(a_limbs: jax.Array, b_limbs: jax.Array,
                method: str = "auto",
                b_const: int | None = None) -> jax.Array:
    """(..., m) uint32 limbs x2 -> (..., 2m) uint32 limbs (full product).

    ``b_const``, when given, asserts that b_limbs holds the HOST-KNOWN
    value b_const in every lane; the NTT tier then multiplies against
    the prepared-operand cache (one forward transform per launch instead
    of two -- kernels/ntt_mul.prepared_operand).  Other methods ignore
    it, so callers can pass it unconditionally for any fixed operand.
    """
    m = a_limbs.shape[-1]
    if method == "auto":
        batch = 1
        for d in a_limbs.shape[:-1]:
            batch *= int(d)
        method = select_method(32 * m, batch=batch)
    if method in ("pallas", "pallas_mxu", "pallas_kara", "ntt"):
        # kernel entry points are 2-D (batch, m); imported lazily because
        # the ops modules import core.mul at module level (cycle) -- core
        # depends statically only on the pure-jnp kernels/common helpers
        from repro.resilience import guard as _guard

        a2, lead = _flatten_leading(jnp.asarray(a_limbs, U32))
        b2, _ = _flatten_leading(jnp.asarray(b_limbs, U32))

        def _kernel_tier():
            if method == "pallas":
                from repro.kernels.dot_mul import ops as _k
                return _k.dot_mul_limbs32(a2, b2)
            if method == "pallas_mxu":
                from repro.kernels.mxu_mul import ops as _k
                return _k.mxu_mul_limbs32(a2, b2)
            if method == "ntt":
                from repro.kernels.ntt_mul import ops as _k
                if b_const is not None and _k.operand_cache_capacity() > 0:
                    return _k.ntt_mul_limbs32_prepared(a2, b_const)
                return _k.ntt_mul_limbs32(a2, b2)
            from repro.kernels.kara_mul import ops as _k
            return _k.kara_mul_limbs32(a2, b2)

        # the jnp fallback mirrors the kernel's algorithmic family: the
        # single-launch VnC / Toeplitz kernels degrade to the jnp VnC
        # composition, the fused Karatsuba / NTT tiers to jnp Karatsuba
        # (quadratic "dot" at those widths would be the real outage)
        fb = "dot" if method in ("pallas", "pallas_mxu") else "karatsuba"

        def _reference_tier():
            def _host(a_np, b_np):
                from repro.core import limbs as _L
                prods = [x * y for x, y in
                         zip(_L.batch_to_ints(np.asarray(a_np)),
                             _L.batch_to_ints(np.asarray(b_np)))]
                return _L.ints_to_batch(prods, 2 * m)
            shape = jax.ShapeDtypeStruct((a2.shape[0], 2 * m), np.uint32)
            return jax.pure_callback(_host, shape, a2, b2, vmap_method="sequential")

        out = _guard.run("mul", 32 * m, [
            (method, _kernel_tier),
            (fb, lambda: mul_limbs32(a2, b2, method=fb)),
            ("reference", _reference_tier),
        ])
        return out.reshape(lead + (2 * m,))
    a_d = split_digits(a_limbs, DIGIT_BITS)
    b_d = split_digits(b_limbs, DIGIT_BITS)
    if method == "dot":
        p = dot_mul(a_d, b_d)
    elif method == "mxu":
        a7 = split_digits(a_limbs, MXU_DIGIT_BITS)
        b7 = split_digits(b_limbs, MXU_DIGIT_BITS)
        p7 = dot_mul_mxu(a7, b7)
        return join_digits(p7, MXU_DIGIT_BITS, 2 * m)
    elif method == "schoolbook":
        p = mul_schoolbook(a_d, b_d)
    elif method == "karatsuba":
        p = mul_karatsuba(a_d, b_d)
    else:
        raise ValueError(
            f"unknown multiply method {method!r}; choose from "
            f"{('auto',) + MUL_METHODS} (REPRO_MUL_BACKEND accepts the "
            f"same names, minus 'auto')")
    return join_digits(p, DIGIT_BITS, 2 * m)


@functools.partial(jax.jit, static_argnames=("method",))
def mul_jit(a_limbs: jax.Array, b_limbs: jax.Array, method: str = "auto"):
    return mul_limbs32(a_limbs, b_limbs, method)
