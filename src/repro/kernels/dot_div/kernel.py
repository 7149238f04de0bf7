"""Fused Pallas TPU kernel for batched schoolbook (Knuth-D) division.

One program owns a (TB, wa) dividend block and a (TB, nb) normalized
divisor block in VMEM and runs the FULL long division there: wa
digit-serial steps, each one trial-quotient estimate + multiply-subtract
+ branch-free add-back, with the (TB, nb+1) partial remainder never
leaving vregs.  The division twin of dot_modmul's fused CIOS loop (the
digit-serial dependency chain is inherent; everything inside a step is
full-width VPU work over the batch tile).

Inputs are PRE-NORMALIZED by the ops wrapper (Knuth's condition, pushed
to the array top so every trial position is static):

  * b_norm = b << s with the top BIT of the array set, so the leading
    digit b_top >= D/2 for every lane -- the bound that makes the
    two-digit trial estimate q_hat = (r1*D + r0) / b_top off by AT MOST
    +2 (Knuth TAoCP 4.3.1 Theorem B), never low.
  * a_s = a << s (widened by nb digits so the shift cannot overflow).
    q = a_s / b_norm is exactly a / b; r_norm = a_s mod b_norm is
    (a mod b) << s, un-shifted by the wrapper.

In-kernel schedule per step t (MSB-first over dividend digits):
  P1 shift-in   : r <- r*D + a_digit (static slice concat; r < b*D).
  P2 estimate   : q_hat from the top two remainder digits vs b_top
                  (one uint32 divide per lane -- the only divide in the
                  whole subsystem's inner loops).
  P3 mul-sub    : r <- r - q_hat*b via lazy lo/hi products, ONE
                  normalize, radix-complement subtract; the carry out
                  of the top digit flags a negative result.
  P4 add-back   : two unrolled masked corrections (q_hat -= 1,
                  r += b_norm); Knuth's bound proves two always suffice.

b == 0 lanes are undefined (the wrapper documents this; the estimate's
divide-by-zero is masked by substituting b_top = 1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.common.carry import add_lane0, normalize_static

U32 = jnp.uint32
DMASK = np.uint32(0xFFFF)
DBITS = np.uint32(16)

# Live (TB, ~nb) u32 arrays per step: a, b, q columns, partial remainder,
# lazy product pair, complement temps, normalize temps.
LIVE_U32_ARRAYS = 16
MAX_TILE = 256


def _sub_flag(r, t):
    """(r - t mod D**w, ge) on (TB, w) normalized digit blocks.

    Radix-complement add over w+1 digits; the top digit of the
    normalized sum is 1 iff r >= t (no borrow).
    """
    tb, w = r.shape
    comp = DMASK - t
    s = jnp.concatenate([r + comp, jnp.zeros((tb, 1), U32)], axis=1)
    s = normalize_static(add_lane0(s, np.uint32(1)), 16,
                         bound=(1 << 17) + 2)
    return s[:, :w], s[:, w:w + 1]


def div_step(r, ain, b, b_top):
    """One Knuth-D step: returns (new remainder, quotient digit).

    r: (TB, nb+1) partial remainder < b_norm; ain: (TB, 1) next dividend
    digit; b: (TB, nb) normalized divisor; b_top: (TB, 1) leading digit
    (>= D/2, or the masked stand-in 1 for zero divisors).
    """
    tb, nb1 = r.shape
    nb = nb1 - 1
    # P1: r*D + ain.  r < b < D**nb so the dropped top digit is 0.
    r = jnp.concatenate([ain, r[:, :nb]], axis=1)
    # P2: two-digit trial estimate, clamped to the digit range.
    num = (r[:, nb:nb + 1] << DBITS) | r[:, nb - 1:nb]
    qh = num // b_top
    qh = jnp.where(qh > DMASK, DMASK, qh)       # (Mosaic has no unsigned min)
    # P3: r - qh*b with lazy products and one static resolve.
    prod = qh * b                                   # (TB, nb) exact uint32
    z1 = jnp.zeros((tb, 1), U32)
    t = (jnp.concatenate([prod & DMASK, z1], axis=1)
         + jnp.concatenate([z1, prod >> DBITS], axis=1))
    t = normalize_static(t, 16, bound=1 << 17)      # qh*b, < D**(nb+1)
    u, ge = _sub_flag(r, t)
    # P4: at most two add-backs (Knuth: qh <= q + 2, never < q).
    for _ in range(2):
        fix = (ge == 0).astype(U32)                 # (TB, 1)
        qh = qh - fix
        # lazy add + one resolve; the carry out of digit nb+1 means the
        # offset representation wrapped, i.e. r is non-negative again.
        add = jnp.concatenate(
            [u + jnp.pad(b * fix, ((0, 0), (0, 1))),
             jnp.zeros((tb, 1), U32)], axis=1)
        add = normalize_static(add, 16, bound=(1 << 17) + 1)
        u = jnp.where(fix == 1, add[:, :nb + 1], u)
        ge = jnp.where(fix == 1, add[:, nb + 1:nb + 2], ge)
    return u, qh


def make_div_kernel(wa: int, nb: int):
    """Kernel body for a (TB, wa) dividend over a (TB, nb) divisor."""

    def div_kernel(a_ref, b_ref, q_ref, r_ref):
        a = a_ref[...]                              # (TB, wa) shifted dividend
        b = b_ref[...]                              # (TB, nb) normalized
        tb = a.shape[0]
        b_top = b[:, nb - 1:nb]
        b_top = jnp.where(b_top == 0, np.uint32(1), b_top)  # mask zero divisors

        def step(_, carry):
            # MSB-first digit serial: the next dividend digit sits in the
            # top lane of a copy of a rotated up once per step, and each
            # quotient digit enters q at lane 0 (static slices only)
            r, q, a_rot = carry
            r, qh = div_step(r, a_rot[:, wa - 1:wa], b, b_top)
            q = jnp.concatenate([qh, q[:, :wa - 1]], axis=1)
            a_rot = jnp.concatenate([a_rot[:, wa - 1:], a_rot[:, :wa - 1]],
                                    axis=1)
            return r, q, a_rot

        r, q, _ = jax.lax.fori_loop(
            0, wa, step,
            (jnp.zeros((tb, nb + 1), U32), jnp.zeros((tb, wa), U32), a))
        q_ref[...] = q
        r_ref[...] = r[:, :nb]

    return div_kernel


@functools.lru_cache(maxsize=64)
def make_call(batch_tile: int, wa: int, nb: int, grid: int, interpret: bool):
    """pallas_call for the fused long division.

    Inputs: a_s (grid*TB, wa), b_norm (grid*TB, nb).  Outputs: the
    little-endian quotient (grid*TB, wa) and the still-shifted remainder
    (grid*TB, nb).
    """
    return pl.pallas_call(
        make_div_kernel(wa, nb),
        grid=(grid,),
        in_specs=[pl.BlockSpec((batch_tile, wa), lambda i: (i, 0)),
                  pl.BlockSpec((batch_tile, nb), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((batch_tile, wa), lambda i: (i, 0)),
                   pl.BlockSpec((batch_tile, nb), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((grid * batch_tile, wa), U32),
                   jax.ShapeDtypeStruct((grid * batch_tile, nb), U32)],
        interpret=interpret,
    )
