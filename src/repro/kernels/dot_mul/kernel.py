"""Pallas TPU kernel for the DoT base-case multiplication (Algorithm 2).

One program multiplies a (TB,) batch tile of m-digit operands (radix
2**16 in uint32 -- the TPU twin of IFMA's 52-in-64).  The five phases:

  P1 gather   : implicit -- row i of the product triangle is a[:, i] * b
                (vectorized over the batch tile; every row independent).
  P2 products : one uint32 VPU multiply per row + lo/hi mask/shift
                (exactly simd_mul_lo / simd_mul_hi).
  P3 align    : static zero-padded adds place lo at columns [i, i+m) and
                hi at [i+1, i+m+1) -- the skew without data movement
                (common/vnc.vnc_cols_rows).
  P4 reduce   : those adds ARE the column reduction (deferred carries;
                column sums < 2m * 2**16 << 2**32, provably no overflow).
  P5 carry    : two deferred-carry passes bring digits to <= 2**16, then
                an unrolled Kogge-Stone tail resolves the 0/1 residue --
                branch-free, unlike the sequential scan of Algorithm 2
                line 38 (the paper's own Phase-4 trick, reused here).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common.carry import normalize_static
from repro.kernels.common.vnc import vnc_cols_rows

U32 = jnp.uint32

# The (TB, 2m) column accumulator plus operands, products, and the
# normalize temps -- counted in (TB, m)-array equivalents for the
# common/tiling VMEM budget.
LIVE_U32_ARRAYS = 24
MAX_TILE = 256


def mul_kernel(a_ref, b_ref, p_ref):
    a = a_ref[...]                           # (TB, m) digits < 2**16
    b = b_ref[...]
    p_ref[...] = normalize_static(vnc_cols_rows(a, b))   # P2-P4, then P5


def make_call(batch_tile: int, m: int, grid: int, interpret: bool):
    return pl.pallas_call(
        mul_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((batch_tile, m), lambda i: (i, 0)),
                  pl.BlockSpec((batch_tile, m), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((batch_tile, 2 * m), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((grid * batch_tile, 2 * m), U32),
        interpret=interpret,
    )
