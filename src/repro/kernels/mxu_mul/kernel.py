"""Pallas TPU kernel for the MXU Toeplitz multiplication path.

The column sums of a digit product ARE a convolution, and a convolution
is a banded-Toeplitz matmul: cols[c] = sum_{i+j=c} a_i * b_j =
(a as 1 x m) @ T with T[i, i+j] = b_j.  With radix-2**7 digits in int8
and int32 accumulation this is a native MXU contraction -- the 128x128
systolic grid computes every partial product as an independent MAC cell,
the genuinely TPU-native realization of the paper's VnC insight (the
beyond-paper path of core/mul.dot_mul_mxu, now fused into one launch).

In-kernel schedule per program (one (TB, m) int8 block of each operand):
  P1/P2  T = skew(broadcast b)       -- static reshape, no data movement
  P3/P4  cols = a @ T                -- int8 x int8 -> int32 on the MXU
         (batched dot_general: every batch row has its own Toeplitz band)
  P5     static carry normalization at digit_bits=7; column sums are
         < m * 127**2, so the deferred-carry pass count computed from
         that bound (3 passes for m <= 2**13) plus the Kogge-Stone tail
         resolves exactly -- one resolve, in VMEM, like every other
         kernel in this family.

Output digits are normalized radix-2**7 values in uint32 (the storage
convention of core/mul.dot_mul_mxu after its normalize).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common.carry import normalize_static
from repro.kernels.common.vnc import skew as _skew

U32 = jnp.uint32
I32 = jnp.int32
MXU_DIGIT_BITS = 7

# Dominant VMEM term is the per-row Toeplitz band: ~2*m*m int8 bytes per
# batch element (see ops._heuristic_tile; the common per-(TB*m) budget
# formula does not capture the quadratic term).


def make_mxu_kernel(m: int):
    def mxu_mul_kernel(a_ref, b_ref, out_ref):
        a = a_ref[...]                            # (TB, m) int8 digits < 2**7
        b = b_ref[...]
        tb = a.shape[0]
        bt = jnp.broadcast_to(b[:, None, :], (tb, m, m))
        T = _skew(bt)                             # (TB, m, 2m-1) int8
        cols = jax.lax.dot_general(
            a, T, dimension_numbers=(((1,), (1,)), ((0,), (0,))),
            preferred_element_type=I32)           # (TB, 2m-1) on the MXU
        cols = jnp.concatenate(
            [cols, jnp.zeros((tb, 1), I32)], axis=1).astype(U32)
        out_ref[...] = normalize_static(
            cols, MXU_DIGIT_BITS, bound=m * 127 * 127 + 1)

    return mxu_mul_kernel


@functools.lru_cache(maxsize=32)
def make_call(batch_tile: int, m: int, grid: int, interpret: bool):
    return pl.pallas_call(
        make_mxu_kernel(m),
        name="mxu_mul",
        grid=(grid,),
        in_specs=[pl.BlockSpec((batch_tile, m), lambda i: (i, 0)),
                  pl.BlockSpec((batch_tile, m), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((batch_tile, 2 * m), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((grid * batch_tile, 2 * m), U32),
        interpret=interpret,
    )
