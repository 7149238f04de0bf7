"""Jit'd wrappers for the fused Montgomery-multiply / modexp kernels.

Mirrors dot_add/ops: interpret mode auto-selected on CPU, batch padded to
the tile size and trimmed after the call.  The Montgomery kernels are
specialized per width only: every lane's modulus, R^2 mod n and n0p ride
along as run-time operands (``_lane_operands``), so lanes of one batch may
work modulo different keys of one width and one compile serves them all.

``dot_mod_exp`` is the fused full-ladder windowed modexp: the exponent
bits are packed into k-ary window values host/jnp-side and the ENTIRE
constant-time ladder (power table build, all squarings, branch-free
table selects, Montgomery entry/exit) runs inside ONE kernel launch
whose (TB, m) residue and (2**w, TB, m) power table stay VMEM-resident
throughout -- versus the PR-3 driver's two launches per exponent bit.

Accepts any Montgomery context exposing ``m / n0p / n_digits /
r2_digits``: one modulus for the batch (core.modular.MontCtx: an int and
(m,) rows) or one a lane (core.modular.MontLanes: (batch,) and (batch, m)
arrays); kept duck-typed so the kernel layer has no dependency on the
dispatch layer built on top of it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.dot_bignum import pick_modexp_window
from repro.kernels.common import autotune, tiling
from repro.kernels.common.runtime import auto_interpret as _auto_interpret
from repro.kernels.common.windows import exponent_windows
from repro.kernels.dot_modmul import kernel as K
from repro.resilience import inject as _inject

U32 = jnp.uint32

# Lazy-digit overflow bound (see core/modular.py): digits < 5*m*2**16
# must stay below 2**32.
MAX_DIGITS = 1 << 13


def _tile_for(m: int, batch: int) -> int:
    return tiling.batch_tile(
        m, batch, budget=tiling.budget_words(K.LIVE_U32_ARRAYS),
        max_tile=K.MAX_TILE)


def _lane_operands(ctx, batch: int):
    """Each lane's modulus (batch, m), R^2 mod n (batch, m) and n0p
    (batch, 1), broadcast from a context that holds one modulus or one
    a lane."""
    m = ctx.m
    return (jnp.broadcast_to(jnp.asarray(ctx.n_digits, U32), (batch, m)),
            jnp.broadcast_to(jnp.asarray(ctx.r2_digits, U32), (batch, m)),
            jnp.broadcast_to(jnp.asarray(ctx.n0p, U32).reshape(-1, 1),
                             (batch, 1)))


def _pad_lanes(pad: int, zeros=(), repeat=()):
    """Pad every array to ``pad`` more lanes: ``zeros`` with zeros,
    ``repeat`` (moduli and their constants) with copies of the last
    lane, so a padded lane still works modulo a valid modulus."""
    return ([jnp.pad(x, ((0, pad), (0, 0))) for x in zeros],
            [jnp.pad(x, ((0, pad), (0, 0)), mode="edge") for x in repeat])


@functools.partial(jax.jit, static_argnames=("tb", "interpret"))
def _mont_mul_call(a, b, n_rows, n0p_col, tb: int, interpret: bool):
    batch, m = a.shape
    pad = (-batch) % tb
    if pad:
        (a, b), (n_rows, n0p_col) = _pad_lanes(pad, (a, b),
                                               (n_rows, n0p_col))
    grid = a.shape[0] // tb
    out = K.make_call(tb, m, grid, interpret)(a, b, n_rows, n0p_col)
    return out[:batch]


@functools.partial(jax.jit, static_argnames=("tb", "window", "interpret"))
def _ladder_call(base, wins, n_rows, r2_rows, n0p_col, tb: int,
                 window: int, interpret: bool):
    batch, m = base.shape
    pad = (-batch) % tb
    if pad:
        # padded lanes exponentiate to 0**0 = 1 and are trimmed below
        (base, wins), (n_rows, r2_rows, n0p_col) = _pad_lanes(
            pad, (base, wins), (n_rows, r2_rows, n0p_col))
    grid = base.shape[0] // tb
    out = K.make_ladder_call(tb, m, grid, window, wins.shape[-1],
                             interpret)(base, wins, n_rows, r2_rows, n0p_col)
    return out[:batch]


def dot_mont_mul(a, b, ctx, interpret=None):
    """(batch, m) digit arrays x2 -> (batch, m) of a*b*R^{-1} mod n."""
    _inject.fire("kernels/dot_modmul/mont_mul")
    assert ctx.m <= MAX_DIGITS, "lazy digits overflow uint32 beyond 2**13"
    a = jnp.asarray(a, U32)
    b = jnp.asarray(b, U32)
    batch, m = a.shape
    n_rows, _, n0p_col = _lane_operands(ctx, batch)
    interpret = _auto_interpret(interpret)
    tb = autotune.pick_tile(
        "dot_modmul", (m, batch, 16, interpret),
        _tile_for(m, batch), batch,
        run=lambda t: _mont_mul_call(a, b, n_rows, n0p_col, t, interpret),
        max_tile=K.MAX_TILE)
    return _mont_mul_call(a, b, n_rows, n0p_col, tb, interpret)


@functools.partial(jax.jit, static_argnames=("tb", "interpret"))
def _barrett_mul_call(a, b, n_row, mu_row, tb: int, interpret: bool):
    batch, m = a.shape
    pad = (-batch) % tb
    if pad:
        a = jnp.pad(a, ((0, pad), (0, 0)))
        b = jnp.pad(b, ((0, pad), (0, 0)))
    grid = a.shape[0] // tb
    out = K.make_barrett_call(tb, m, grid, interpret)(a, b, n_row, mu_row)
    return out[:batch]


@functools.partial(jax.jit, static_argnames=("tb", "window", "interpret"))
def _barrett_ladder_call(base, wins, n_row, mu_row, tb: int, window: int,
                         interpret: bool):
    batch, m = base.shape
    pad = (-batch) % tb
    if pad:
        base = jnp.pad(base, ((0, pad), (0, 0)))
        # padded lanes exponentiate to 0**0 = 1 and are trimmed below
        wins = jnp.pad(wins, ((0, pad), (0, 0)))
    grid = base.shape[0] // tb
    out = K.make_barrett_ladder_call(tb, m, grid, window, wins.shape[-1],
                                     interpret)(base, wins, n_row, mu_row)
    return out[:batch]


def dot_barrett_mul(a, b, ctx, interpret=None):
    """(batch, m) digit arrays x2 -> (batch, m) of a*b mod n via the
    fused Barrett kernel (no Montgomery form; any modulus parity).

    ``ctx`` is duck-typed on ``m / n_digits / mu_digits``
    (core.modular.BarrettCtx); n and mu ride in as runtime rows, so one
    compiled kernel serves every same-width modulus."""
    _inject.fire("kernels/dot_modmul/barrett_mul")
    assert ctx.m <= MAX_DIGITS, "lazy digits overflow uint32 beyond 2**13"
    a = jnp.asarray(a, U32)
    b = jnp.asarray(b, U32)
    n_row = jnp.asarray(ctx.n_digits, U32)[None, :]
    mu_row = jnp.asarray(ctx.mu_digits, U32)[None, :]
    interpret = _auto_interpret(interpret)
    batch, m = a.shape
    tb = autotune.pick_tile(
        "dot_barrett_mul", (m, batch, 16, interpret),
        tiling.batch_tile(
            m, batch, budget=tiling.budget_words(K.BARRETT_LIVE_U32_ARRAYS),
            max_tile=K.MAX_TILE),
        batch,
        run=lambda t: _barrett_mul_call(a, b, n_row, mu_row, t, interpret),
        max_tile=K.MAX_TILE)
    return _barrett_mul_call(a, b, n_row, mu_row, tb, interpret)


def dot_barrett_mod_exp(base, exp_bits, ctx, window=None, interpret=None):
    """Fused full-ladder windowed modexp via Barrett reduction: the even-
    modulus twin of dot_mod_exp (same one-launch constant-time schedule,
    no Montgomery entry/exit).  ``ctx`` duck-typed as dot_barrett_mul."""
    _inject.fire("kernels/dot_modmul/barrett_mod_exp")
    assert ctx.m <= MAX_DIGITS, "lazy digits overflow uint32 beyond 2**13"
    base = jnp.asarray(base, U32)
    eb = jnp.asarray(exp_bits, U32)
    if eb.ndim == 1:
        eb = jnp.broadcast_to(eb, (base.shape[0], eb.shape[-1]))
    w = int(window if window is not None
            else pick_modexp_window(eb.shape[-1]))
    wins = exponent_windows(eb, w)
    n_row = jnp.asarray(ctx.n_digits, U32)[None, :]
    mu_row = jnp.asarray(ctx.mu_digits, U32)[None, :]
    interpret = _auto_interpret(interpret)
    batch, m = base.shape
    # heuristic tile only, for the same reason as dot_mod_exp
    tb = tiling.batch_tile(
        m, batch, budget=tiling.budget_words(K.barrett_live_arrays(w)),
        max_tile=K.MAX_TILE)
    return _barrett_ladder_call(base, wins, n_row, mu_row, tb, w, interpret)


def dot_mod_exp(base, exp_bits, ctx, window=None, interpret=None):
    """(batch, m) digits ** exp -> (batch, m) digits of base**e mod n,
    the whole windowed ladder fused into ONE kernel launch.

    exp_bits: (nbits,) or (batch, nbits) bits MSB-first (uint32/int32);
    per-lane exponents share nbits but may differ per batch element, and
    so may the moduli (a per-lane ``ctx``).  ``window`` overrides the
    config-picked window size w.  Constant-time in structure: exponent
    windows feed one-hot selects, never branches.
    """
    _inject.fire("kernels/dot_modmul/mod_exp")
    assert ctx.m <= MAX_DIGITS, "lazy digits overflow uint32 beyond 2**13"
    base = jnp.asarray(base, U32)
    eb = jnp.asarray(exp_bits, U32)
    if eb.ndim == 1:
        eb = jnp.broadcast_to(eb, (base.shape[0], eb.shape[-1]))
    w = int(window if window is not None
            else pick_modexp_window(eb.shape[-1]))
    wins = exponent_windows(eb, w)
    batch, m = base.shape
    n_rows, r2_rows, n0p_col = _lane_operands(ctx, batch)
    interpret = _auto_interpret(interpret)
    # Heuristic tile only: the 2**w-row power table inflates the live
    # working set (ladder_live_arrays), and a timed autotune sweep would
    # re-run the WHOLE ladder per candidate -- not worth it for a kernel
    # whose launch count is already 1 per modexp.
    tb = tiling.batch_tile(
        m, batch, budget=tiling.budget_words(K.ladder_live_arrays(w)),
        max_tile=K.MAX_TILE)
    return _ladder_call(base, wins, n_rows, r2_rows, n0p_col, tb, w,
                        interpret)
