"""Jit'd wrappers for the fused Karatsuba-over-VnC kernel.

Same conventions as the other kernel wrappers: interpret mode auto-
selected on CPU, batch padded to the tile and trimmed, tile chosen
outside jit by the common heuristic/autotuner.  The 32-bit limb entry
point pays the radix conversion at entry/exit (paper sec 3.3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import autotune, tiling
from repro.kernels.common.runtime import auto_interpret as _auto_interpret
from repro.kernels.kara_mul import kernel as K
from repro.resilience import inject as _inject

U32 = jnp.uint32


def _heuristic_tile(m: int, batch: int,
                    threshold: int = K.DEFAULT_THRESHOLD) -> int:
    return tiling.batch_tile(
        m, batch, budget=tiling.budget_words(K.live_arrays(m, threshold)),
        max_tile=K.MAX_TILE)


@functools.partial(jax.jit, static_argnames=("tb", "threshold", "base_mode",
                                             "interpret"))
def _call(a, b, tb: int, threshold: int, base_mode: str, interpret: bool):
    batch, m = a.shape
    pad = (-batch) % tb
    if pad:
        a = jnp.pad(a, ((0, pad), (0, 0)))
        b = jnp.pad(b, ((0, pad), (0, 0)))
    grid = a.shape[0] // tb
    p = K.make_call(tb, m, grid, threshold, base_mode, interpret)(a, b)
    return p[:batch]


def kara_mul_digits(a_digits, b_digits, interpret=None,
                    threshold: int = K.DEFAULT_THRESHOLD,
                    base_mode: str | None = None):
    """(batch, m) uint32 radix-2**16 digits -> (batch, 2m) digits.

    m <= 256 (4096 bits); the whole Karatsuba tree runs in one launch.
    base_mode picks the phase-B schedule (common/vnc.py): the fused row
    loop ("rows", default -- measured fastest on CPU interpret too) or
    the skew contraction ("skew", kept selectable for autotune sweeps).
    """
    a = jnp.asarray(a_digits, U32)
    b = jnp.asarray(b_digits, U32)
    interpret = _auto_interpret(interpret)
    if base_mode is None:
        base_mode = "rows"
    batch, m = a.shape
    tb = autotune.pick_tile(
        "kara_mul", (m, batch, 16, threshold, base_mode, interpret),
        _heuristic_tile(m, batch, threshold), batch,
        run=lambda t: _call(a, b, t, threshold, base_mode, interpret),
        max_tile=K.MAX_TILE)
    return _call(a, b, tb, threshold, base_mode, interpret)


def kara_mul_limbs32(a_limbs, b_limbs, interpret=None,
                     threshold: int = K.DEFAULT_THRESHOLD):
    """(batch, m) uint32 saturated limbs -> (batch, 2m) limbs (full
    product), radix-converted at entry/exit."""
    _inject.fire("kernels/kara_mul")
    from repro.core import mul as coremul
    m = a_limbs.shape[-1]
    a_d = coremul.split_digits(jnp.asarray(a_limbs, U32), 16)
    b_d = coremul.split_digits(jnp.asarray(b_limbs, U32), 16)
    p_d = kara_mul_digits(a_d, b_d, interpret, threshold)
    return coremul.join_digits(p_d, 16, 2 * m)
