"""Pallas TPU kernels for exact deferred-carry gradient accumulation.

Three fused kernels (core/exact_accum.py is the jnp oracle):
  encode_kernel     : f32 tile -> L uint32 digit planes (quantize + split +
                      two's-complement sign extension) in one VMEM pass.
  accum_kernel      : acc += digits, carry-free (input/output aliased; the
                      deferred-carry inner loop of microbatch accumulation).
  finalize_kernel   : carry-resolve (2 deferred passes + Kogge-Stone tail)
                      + two's-complement decode back to f32.

Digit planes are laid out (L, batch_tile, n) so each plane is a clean
(8, 128)-aligned VPU tile; L is tiny (4).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.exact_accum import ExactAccumConfig

U32 = jnp.uint32
I32 = jnp.int32
F32 = jnp.float32


def encode_kernel(x_ref, d_ref, *, cfg: ExactAccumConfig):
    x = x_ref[...]
    q = jnp.round(jnp.clip(x.astype(F32), -cfg.clip, cfg.clip)
                  * (2.0 ** cfg.frac_bits)).astype(I32)
    u = q.astype(U32)
    r = cfg.radix_bits
    mask = np.uint32((1 << r) - 1)
    neg = q < 0
    neg_fill = jnp.where(neg, mask, np.uint32(0))
    for k in range(cfg.num_limbs):
        lo_bit = r * k
        if lo_bit < 32:
            d = u >> np.uint32(lo_bit)
            if lo_bit + r > 32:
                ext_bits = lo_bit + r - 32
                ext = jnp.where(neg, np.uint32((1 << ext_bits) - 1),
                                np.uint32(0))
                d = d | (ext << np.uint32(32 - lo_bit))
            d_ref[k, :, :] = d & mask
        else:
            d_ref[k, :, :] = neg_fill


def accum_kernel(acc_ref, d_ref, out_ref):
    # deferred-carry accumulate: one VPU add per plane, NO carry handling.
    out_ref[...] = acc_ref[...] + d_ref[...]


def finalize_kernel(acc_ref, y_ref, *, cfg: ExactAccumConfig):
    acc = acc_ref[...]                       # (L, TB, n)
    r = np.uint32(cfg.radix_bits)
    mask = np.uint32((1 << cfg.radix_bits) - 1)
    L = cfg.num_limbs
    # two deferred-carry passes along the (leading) limb axis
    for _ in range(2):
        carry = acc >> r
        low = acc & mask
        shifted = jnp.concatenate(
            [jnp.zeros_like(carry[:1]), carry[:-1]], axis=0)
        acc = low + shifted
    # Kogge-Stone tail (L is tiny: unrolled pairwise combine)
    g = (acc >> r).astype(U32)
    low = acc & mask
    p = (low == mask).astype(U32)
    d = 1
    while d < L:
        g_sh = jnp.concatenate([jnp.zeros_like(g[:d]), g[:-d]], axis=0)
        p_sh = jnp.concatenate([jnp.ones_like(p[:d]), p[:-d]], axis=0)
        g = g | (p & g_sh)
        p = p & p_sh
        d *= 2
    c = jnp.concatenate([jnp.zeros_like(g[:1]), g[:-1]], axis=0)
    low = (low + c) & mask

    # decode two's complement: complement negatives in the integer domain
    # (f32 cannot represent 2**(rL) - |v| minus 2**(rL) without losing |v|).
    neg = (low[-1] >> np.uint32(cfg.radix_bits - 1)) & np.uint32(1)
    comp = mask - low
    comp = jnp.concatenate(
        [(comp[:1] + np.uint32(1)), comp[1:]], axis=0)
    # resolve the +1 ripple through the complemented digits (KS tail)
    g2 = (comp >> r).astype(U32)
    low2 = comp & mask
    p2 = (low2 == mask).astype(U32)
    d = 1
    while d < L:
        g_sh = jnp.concatenate([jnp.zeros_like(g2[:d]), g2[:-d]], axis=0)
        p_sh = jnp.concatenate([jnp.ones_like(p2[:d]), p2[:-d]], axis=0)
        g2 = g2 | (p2 & g_sh)
        p2 = p2 & p_sh
        d *= 2
    c2 = jnp.concatenate([jnp.zeros_like(g2[:1]), g2[:-1]], axis=0)
    mag = (low2 + c2) & mask
    digits = jnp.where(neg[None] == 1, mag, low)
    val = jnp.zeros(low.shape[1:], F32)
    for k in reversed(range(L)):
        val = val * float(1 << cfg.radix_bits) + digits[k].astype(F32)
    val = jnp.where(neg == 1, -val, val)
    y_ref[...] = val * (2.0 ** -cfg.frac_bits)


def make_encode(cfg, tb, n, grid, interpret):
    return pl.pallas_call(
        functools.partial(encode_kernel, cfg=cfg),
        name="exact_accum_encode",
        grid=(grid,),
        in_specs=[pl.BlockSpec((tb, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((cfg.num_limbs, tb, n), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((cfg.num_limbs, grid * tb, n), U32),
        interpret=interpret,
    )


def make_accum(L, tb, n, grid, interpret):
    spec = pl.BlockSpec((L, tb, n), lambda i: (0, i, 0))
    return pl.pallas_call(
        accum_kernel,
        name="exact_accum_add",
        grid=(grid,),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((L, grid * tb, n), U32),
        input_output_aliases={0: 0},
        interpret=interpret,
    )


def make_finalize(cfg, tb, n, grid, interpret):
    return pl.pallas_call(
        functools.partial(finalize_kernel, cfg=cfg),
        name="exact_accum_finalize",
        grid=(grid,),
        in_specs=[pl.BlockSpec((cfg.num_limbs, tb, n), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((tb, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((grid * tb, n), F32),
        interpret=interpret,
    )
