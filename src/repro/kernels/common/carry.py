"""Shared in-kernel carry machinery for every DoT Pallas kernel.

These are the three primitives the paper's Phase-4/Phase-5 tricks reduce
to on TPU, previously copy-pasted across dot_add / dot_mul / dot_modmul
(PR 1 left dot_mul importing from dot_add and dot_modmul importing from
dot_mul -- a dependency chain between sibling kernels).  They live here
now; every kernel imports from ``repro.kernels.common.carry`` and no
kernel depends on another kernel package.

All helpers are branch-free with STATIC control flow (Python loops
unrolled at trace time), which is what makes them kernel-safe: inside a
``pallas_call`` body there is no ``lax.while_loop`` over a data-dependent
carry count, so convergence bounds must be proven at build time instead
of checked at run time.

The Mosaic TPU compiler lowers neither scatter (``x.at[...].add``) nor
``dynamic_slice``, so kernels place lane windows with ``add_at`` (a
zero-padded concatenate plus an add) and touch lane 0 with ``add_lane0``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

U32 = jnp.uint32


def add_at(dst, start: int, src):
    """dst[..., start:start+w] += src for a static start, as a full-width
    add of src zero-padded along the last axis (kernel-safe: no scatter)."""
    width, w = dst.shape[-1], src.shape[-1]
    rest = width - start - w
    assert start >= 0 and rest >= 0, "window outside the destination"
    lead = src.shape[:-1]
    parts = ([jnp.zeros(lead + (start,), src.dtype)] if start else []) \
        + [src] + ([jnp.zeros(lead + (rest,), src.dtype)] if rest else [])
    return dst + (jnp.concatenate(parts, axis=-1) if len(parts) > 1
                  else src)


def add_lane0(x, v):
    """x with v added to digit 0 of the last axis (v a scalar or (..., 1))."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return x + jnp.where(lane == 0, v, np.uint32(0)).astype(x.dtype)


def rotate_down(x):
    """Digit i+1 moves to lane i and digit 0 wraps to the top: reading
    lane 0 after k rotations yields digit k, a static slice where a
    ``fori_loop`` would otherwise need a dynamic lane slice."""
    return jnp.concatenate([x[..., 1:], x[..., :1]], axis=-1)


def ks_scan_unrolled(g, p):
    """Inclusive (generate, propagate) prefix scan along the last axis,
    unrolled into log2(m) shift rounds (identity element: g=0, p=1).

    The Kogge-Stone carry network of DoT-add Phase 4', reused by every
    kernel that must resolve a residual 0/1 carry without a sequential
    pass.
    """
    m = g.shape[-1]
    d = 1
    while d < m:
        g_sh = jnp.concatenate(
            [jnp.zeros_like(g[..., :d]), g[..., :-d]], axis=-1)
        p_sh = jnp.concatenate(
            [jnp.ones_like(p[..., :d]), p[..., :-d]], axis=-1)
        g = g | (p & g_sh)
        p = p & p_sh
        d *= 2
    return g, p


def shift_up(c):
    """One-digit shift toward the most significant end (carry landing)."""
    return jnp.concatenate(
        [jnp.zeros_like(c[..., :1]), c[..., :-1]], axis=-1)


def normalize_static(cols, digit_bits: int = 16, bound: int = 1 << 32):
    """Exact carry normalization with static control flow (kernel-safe).

    cols holds lazy (deferred-carry) digits in uint32: the represented
    value is sum(cols[i] * 2**(digit_bits*i)) with each digit < ``bound``.
    Deferred-carry vector passes ``c <- (c & mask) + shift_up(c >> bits)``
    run until the per-digit bound is provably <= 2*mask + 1 (so the
    remaining carry is 0/1); the pass count is computed from ``bound`` at
    trace time, not from the data.  An unrolled Kogge-Stone tail then
    resolves the 0/1 residue branch-free (the paper's own Phase-4 trick,
    applied to Phase 5).

    The value is preserved modulo 2**(digit_bits*len): callers must size
    the array so the true result fits (every kernel here does, see the
    per-kernel bound notes).
    """
    assert 1 <= digit_bits <= 16, "digit products must fit in uint32"
    mask = np.uint32((1 << digit_bits) - 1)
    bits = np.uint32(digit_bits)
    b = int(bound)
    assert b <= 1 << 32, "lazy digits must fit in uint32"
    while b > 2 * int(mask) + 1:
        cols = (cols & mask) + shift_up(cols >> bits)
        b = int(mask) + (b >> digit_bits)
    g = (cols >> bits).astype(U32)           # residual carry, in {0, 1}
    low = cols & mask
    p = (low == mask).astype(U32)
    G, _ = ks_scan_unrolled(g, p)
    return (low + shift_up(G)) & mask
