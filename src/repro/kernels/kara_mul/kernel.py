"""Fused Karatsuba-over-VnC Pallas kernel (one launch, one carry resolve).

The jnp composition in core/mul.py (``mul_karatsuba`` over ``dot_mul``)
pays per recursion level: every node normalizes its product columns with
a data-dependent while-loop, every operand difference runs the
radix-complement machinery of ``digit_sub_abs`` (two more normalizes and
a sign select), and every base case is a separate skew/reduce.  The DoTMP
observation (paper sec 3.3) is that the base-case multiply compounds
through the recursion; this kernel compounds the LAZY-DIGIT idea through
it instead: the whole Karatsuba tree for one batch tile runs inside a
single program, product columns stay deferred-carry uint32 end-to-end,
and exactly ONE static carry resolve happens at the very end.

Three tricks make that possible:

1. **Sum variant + static subtraction.**  We use the
   (a_l + a_h)(b_l + b_h) middle product (sums, not |differences|: no
   data-dependent signs), so the only subtraction is the structural
   ``- p0 - p1`` in the recombination.  A lazy column vector c with
   digits < K is subtracted branch-free by ADDING the per-digit
   complement (K - c[i]): that adds the static constant K * (1 + B +
   ... + B^(L-1)) minus the value of c.  Every such constant is a plain
   Python int computed at trace time; their total CONST is cancelled at
   the end by adding the digits of B^Lp - CONST and letting the known
   B^Lp marker fall off the top -- one constant add, zero selects.

2. **Batched base cases.**  The recursion is resolved at trace time into
   its 3^depth leaf multiplies, whose operands (halves and normalized
   half-sums) are gathered into one (P*TB, nb) block; a single VnC row
   loop of nb unrolled steps computes ALL leaf products at once (the
   multiplicative twin of batching independent adds over VPU lanes).

3. **Static overflow accounting.**  Every node tracks a trace-time bound
   on its lazy column digits; the build asserts the final bound stays
   under 2**31, which is what licenses the single end resolve (see
   common/carry.normalize_static).  For 512..4096-bit operands (m = 32..
   256 radix-2**16 digits, threshold 48) the worst bound is ~2**28.

The only per-level carry work left is normalizing the half-SUMS (k+1-wide
operands must be < 2**16 before they can be multiplied exactly in
uint32); that is O(log k) static vector steps on k-wide arrays -- nothing
like the 2m-wide while-loop resolves of the jnp path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.common.carry import add_at, normalize_static
from repro.kernels.common.vnc import vnc_cols_rows, vnc_cols_skew

U32 = jnp.uint32
DBITS = 16
DMASK = np.uint32((1 << DBITS) - 1)
BASE = 1 << DBITS

# Leaf width in digits.  48 (not a power of two!) so that the k+1-wide
# half-SUM operands of a 2k-wide node stay leaves instead of spawning a
# whole extra subtree: with threshold 32, the 33-wide sums of a 64-digit
# node split again and the leaf count at 2048 bits jumps from 9 to 19 --
# measured ~2.5x slower despite the smaller leaves (padding + leaf-count
# overhead beats the O(n^1.58) win at these widths).
DEFAULT_THRESHOLD = 48
MAX_DIGITS = 256            # 4096 bits; bound analysis above covers <= 256

# Leaf cols + stacked operands + recombination temps, in (TB, m)-array
# equivalents at depth 0 (see live_arrays for the growth with depth).
LIVE_U32_ARRAYS = 24
MAX_TILE = 128


def live_arrays(m: int, threshold: int = DEFAULT_THRESHOLD) -> int:
    """LIVE_U32_ARRAYS scaled by the leaf blow-up: each Karatsuba level
    turns an n-digit operand into three of ~n/2 digits, so the stacked
    leaves and their columns grow as (3/2)**depth (a 2048-bit tile sized
    for depth 0 overruns the TPU compiler's 16 MiB scoped VMEM)."""
    live = LIVE_U32_ARRAYS
    while m > threshold:
        m = -(-m // 2)
        live = live * 3 // 2
    return live


def _ones_value(length: int) -> int:
    """1 + B + ... + B^(length-1) as a Python int."""
    return ((1 << (DBITS * length)) - 1) // (BASE - 1)


def _leaf_bound(width: int) -> int:
    """Max lazy column digit of a VnC leaf product: <= width lo terms
    (< B) plus width hi terms (< B) per column."""
    return 2 * width * (BASE - 1)


def _norm_sum(x, y):
    """(TB, k) + (TB, k) normalized digits -> (TB, k+1) normalized digits
    of the exact sum (digits of x + y are < 2**17: one static pass + the
    Kogge-Stone tail resolve exactly)."""
    s = x + y
    s = jnp.concatenate([s, jnp.zeros_like(s[:, :1])], axis=1)
    return normalize_static(s, DBITS, bound=1 << (DBITS + 1))


def _collect(x, y, threshold, leaves):
    """Trace-time recursion, phase A: gather every leaf operand pair.

    x, y: (TB, n) NORMALIZED digit arrays.  Returns a static spec tree;
    appends (x_leaf, y_leaf, width) to ``leaves``.  Odd widths above the
    threshold are zero-padded to even (value unchanged; the spec records
    the effective width).
    """
    n = x.shape[1]
    if n > threshold and n % 2:
        z = jnp.zeros_like(x[:, :1])
        x = jnp.concatenate([x, z], axis=1)
        y = jnp.concatenate([y, z], axis=1)
        n += 1
    if n <= threshold:
        idx = len(leaves)
        leaves.append((x, y, n))
        return ("leaf", n, idx)
    k = n // 2
    s0 = _collect(x[:, :k], y[:, :k], threshold, leaves)
    s1 = _collect(x[:, k:], y[:, k:], threshold, leaves)
    sa = _norm_sum(x[:, :k], x[:, k:])
    sb = _norm_sum(y[:, :k], y[:, k:])
    ss = _collect(sa, sb, threshold, leaves)
    return ("split", n, k, s0, s1, ss)


# Phase B (all base multiplies at once, (P*TB, nb) x2 -> (P*TB, 2nb)
# lazy cols): two schedules of the same math, picked per backend -- the
# row loop is the VPU-native form for TPU, the skew contraction avoids
# the serial update chain that dominates in CPU interpret mode.
_BASE_MODES = {"rows": vnc_cols_rows, "skew": vnc_cols_skew}


def _combine(spec, cols, tb: int):
    """Trace-time recursion, phase C: lazy recombination.

    cols holds leaf idx's product columns in rows [idx*tb, (idx+1)*tb).
    Returns (lazy_cols (TB, L), bound, const) with
    value(lazy_cols) == true_product + const, const a static Python int.
    """
    if spec[0] == "leaf":
        _, w, idx = spec
        return cols[idx * tb:(idx + 1) * tb, :2 * w], _leaf_bound(w), 0

    _, n, k, s0, s1, ss = spec
    c0, b0, k0c = _combine(s0, cols, tb)
    c1, b1, k1c = _combine(s1, cols, tb)
    cs, bs, ksc = _combine(ss, cols, tb)
    l0, l1, ls = c0.shape[1], c1.shape[1], cs.shape[1]

    # middle = cs - c0 - c1 via per-digit complements (trick 1): the
    # static offsets K0*S(l0), K1*S(l1) join the node constant.
    lm = max(ls, l0, l1)
    mid = jnp.zeros((tb, lm), U32)
    mid = add_at(mid, 0, cs)
    mid = add_at(mid, 0, np.uint32(b0) - c0)
    mid = add_at(mid, 0, np.uint32(b1) - c1)
    b_mid = bs + b0 + b1
    const_mid = ksc - k0c - k1c + b0 * _ones_value(l0) + b1 * _ones_value(l1)

    lout = max(2 * n, k + lm, 2 * k + l1)
    out = jnp.zeros((tb, lout), U32)
    out = add_at(out, 0, c0)
    out = add_at(out, k, mid)
    out = add_at(out, 2 * k, c1)
    # frames may overlap by a few pad digits; bound conservatively.
    bound = b_mid + b0 + b1
    assert bound + BASE < 1 << 31, \
        "lazy columns would overflow uint32 (width/threshold too large)"
    const = k0c + (const_mid << (DBITS * k)) + (k1c << (DBITS * 2 * k))
    return out, bound, const


def make_kara_kernel(m: int, threshold: int = DEFAULT_THRESHOLD,
                     base_mode: str = "rows"):
    """Kernel body for (TB, m) x (TB, m) -> (TB, 2m) normalized digits."""
    assert m <= MAX_DIGITS, "bound analysis covers <= 256 digits (4096 bits)"
    base_cols = _BASE_MODES[base_mode]

    def kara_kernel(a_ref, b_ref, out_ref):
        a = a_ref[...]                       # (TB, m) digits < 2**16
        b = b_ref[...]
        tb = a.shape[0]

        leaves = []                          # phase A: operand gathering
        spec = _collect(a, b, threshold, leaves)
        nb = max(w for _, _, w in leaves)
        # leaves stacked along the batch (sublane) axis: a 2-D
        # (P*TB, nb) block tiles densely, where a (TB, P, nb) one pads P
        # and nb to the (8, 128) tile
        apad = jnp.concatenate(
            [jnp.pad(x, ((0, 0), (0, nb - w))) for x, _, w in leaves], axis=0)
        bpad = jnp.concatenate(
            [jnp.pad(y, ((0, 0), (0, nb - w))) for _, y, w in leaves], axis=0)

        cols = base_cols(apad, bpad)         # phase B: all base multiplies

        out, bound, const = _combine(spec, cols, tb)   # phase C: lazy recombine
        assert bound + BASE < 1 << 31, "lazy columns would overflow uint32"

        if const == 0:                       # pure base case (m <= threshold)
            final = out
            fbound = bound
        else:
            # cancel CONST: add digits of B^Lp - CONST, then the known
            # B^Lp marker carries out beyond the digits we read back.
            lout = out.shape[1]
            cap = bound * _ones_value(lout)          # max value(out)
            lp = max(lout, -(-cap.bit_length() // DBITS) + 1)
            d = (1 << (DBITS * lp)) - const
            assert 0 < d, "CONST exceeds the correction headroom"
            # the digits of d as a (1, lp+1) row built from scalar
            # selects (pallas kernels cannot capture non-scalar
            # constants); zero digits are skipped at trace time
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, lp + 1), 1)
            drow = jnp.zeros((1, lp + 1), U32)
            for i in range(lp):
                di = (d >> (DBITS * i)) & (BASE - 1)
                if di:
                    drow = jnp.where(lane == i, np.uint32(di), drow)
            final = add_at(jnp.zeros((tb, lp + 1), U32), 0, out) + drow
            fbound = bound + BASE
        norm = normalize_static(final, DBITS, bound=fbound)
        out_ref[...] = norm[:, :2 * m]

    return kara_kernel


@functools.lru_cache(maxsize=32)
def make_call(batch_tile: int, m: int, grid: int, threshold: int,
              base_mode: str, interpret: bool):
    return pl.pallas_call(
        make_kara_kernel(m, threshold, base_mode),
        # no name=: it would rename the HLO instruction (_call) that the
        # benchmark's kernel metric keys on; kernel_name stays the
        # function's name, kara_kernel
        grid=(grid,),
        in_specs=[pl.BlockSpec((batch_tile, m), lambda i: (i, 0)),
                  pl.BlockSpec((batch_tile, m), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((batch_tile, 2 * m), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((grid * batch_tile, 2 * m), U32),
        interpret=interpret,
    )
