"""Pallas TPU kernel for DoT multi-limb addition/subtraction.

Grid: 1-D over batch tiles; each program owns a (TB, m) block of both
operands in VMEM.  The limb axis (m uint32 limbs, little-endian) maps to
VPU lanes; the batch tile maps to sublanes -- the TPU twin of issuing one
AVX-512 instruction across 8 lanes, amortized over thousands of
independent additions.

In-kernel schedule (branch-free; see core/add.py for the lax.cond "rare
slow path" formulation -- inside a kernel the log-depth unconditional
Phase 4 is cheaper than divergence):
  P1  r = a + b                       (one VPU add)
  P2  g = r < a ; p = r == MAX        (carry generate / propagate masks)
  P4' unrolled Kogge-Stone over the limb axis (log2(m) shift/or rounds)
  P3  s = r + shift_up(G)
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.common.carry import ks_scan_unrolled, shift_up

U32 = jnp.uint32
MAX32 = np.uint32(0xFFFFFFFF)

# Simultaneously-live (TB, m) u32 arrays in the kernel body: a, b, r,
# g/p, G, s (see common/tiling.py for how this sizes the batch tile).
LIVE_U32_ARRAYS = 6
MAX_TILE = 512


def add_kernel(a_ref, b_ref, s_ref, c_ref):
    a = a_ref[...]
    b = b_ref[...]
    r = a + b                                   # P1
    g = (r < a).astype(U32)                     # P2
    p = (r == MAX32).astype(U32)
    G, _ = ks_scan_unrolled(g, p)               # P4' (branch-free)
    s_ref[...] = r + shift_up(G)                # P3
    c_ref[...] = G[..., -1:]


def sub_kernel(a_ref, b_ref, s_ref, c_ref):
    a = a_ref[...]
    b = b_ref[...]
    r = a - b
    g = (a < b).astype(U32)                     # borrow generate
    p = (r == np.uint32(0)).astype(U32)        # borrow propagate
    G, _ = ks_scan_unrolled(g, p)
    s_ref[...] = r - shift_up(G)
    c_ref[...] = G[..., -1:]


def make_call(kernel, batch_tile: int, m: int, grid: int,
              interpret: bool):
    return pl.pallas_call(
        kernel,
        name="dot_add",
        grid=(grid,),
        in_specs=[pl.BlockSpec((batch_tile, m), lambda i: (i, 0)),
                  pl.BlockSpec((batch_tile, m), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((batch_tile, m), lambda i: (i, 0)),
                   pl.BlockSpec((batch_tile, 1), lambda i: (i, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((grid * batch_tile, m), U32),
            jax.ShapeDtypeStruct((grid * batch_tile, 1), U32),
        ],
        interpret=interpret,
    )
