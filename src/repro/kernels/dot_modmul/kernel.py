"""Fused Pallas TPU kernel for batched CIOS Montgomery multiplication.

One program owns a (TB, m) block of both operands in VMEM and runs the
FULL Montgomery product there: m CIOS iterations with lazy radix-2**16
digits (deferred carries, per the overflow analysis in core/modular.py),
then ONE carry-resolve pass and the branch-free conditional subtract.
The jnp formulation in core/modular.py round-trips the (m+1)-digit
accumulator through HBM on every scan step; here the accumulator never
leaves vregs -- the TPU twin of the paper's "keep the redundant
representation in registers across the whole CIOS loop" (sec 4.4, DoTSSL)
and of Meng's vectorized-Montgomery generation.

In-kernel schedule per iteration i (all VPU ops over the batch tile):
  P1  acc += a_i * b          (lo into column j, hi into j+1 -- lazy)
  P2  u = (acc_0 mod B) * n0p mod B
  P3  acc += u * n            (digit 0 becomes 0 mod B)
  P4  shift acc down one digit, folding acc_0's high part into the new
      digit 0 (static slice -- no data movement beyond the vreg shuffle)
After m iterations: digits < 5*m*2**16 (safe in uint32 for m <= 2**13),
one normalize_static pass brings t < 2n to normalized digits, and the
radix-complement subtract selects t or t - n without branching.

n0p and m are BAKED into the kernel (host-side Montgomery constants --
one specialization per modulus, exactly the serving pattern: a key is
loaded once, then millions of modmuls reuse the compiled kernel).

``make_ladder_call`` composes the same multiply into the fused
full-ladder windowed modexp kernel: ONE launch runs the entire k-ary
exponentiation (Montgomery entry, 2**w-entry power table build, all
squarings and branch-free one-hot table selects, Montgomery exit) with
everything VMEM-resident -- versus two launches per exponent bit when
the ladder is composed outside the kernel.  Its loops are
lax.fori_loops (see cios_iterations) so compile time stays flat
in nbits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.common.carry import (add_lane0, normalize_static,
                                        rotate_down)

U32 = jnp.uint32
DMASK = np.uint32(0xFFFF)
DBITS = np.uint32(16)

# ~8 live (TB, m+1) u32 arrays in the CIOS loop (a, b, n, acc, two
# product temps, normalize temps) + headroom; sizes the batch tile via
# common/tiling.
LIVE_U32_ARRAYS = 12
MAX_TILE = 256


def cond_subtract(t, n):
    """Branch-free conditional subtract: t if t < n else t - n.

    t: (TB, m+1) normalized digits with t < 2n; n: (1, m) or (TB, m).
    Radix-complement add computes t - n + B**(m+1); the carry out of the
    top digit (1 iff t >= n) selects between the two candidates.
    """
    tb = t.shape[0]
    m = t.shape[1] - 1
    comp = jnp.concatenate(
        [DMASK - n, jnp.full((n.shape[0], 1), DMASK, U32)], axis=1)
    s = add_lane0(t + comp, np.uint32(1))        # lazy, < 2**17 + 1
    ext = jnp.concatenate([s, jnp.zeros((tb, 1), U32)], axis=1)
    sn = normalize_static(ext)                    # (TB, m+2)
    ge = sn[:, m + 1:m + 2]                       # carry out: 1 iff t >= n
    return jnp.where(ge == 1, sn[:, :m], t[:, :m])


def cios_iterations(a, b, n, n0p):
    """The lazy CIOS loop on (TB, m) blocks; returns the (TB, m+1) lazy
    accumulator with t = a*b*R^{-1} represented in deferred-carry digits.

    The digit loop (the dependency chain inherent to Montgomery) is a
    lax.fori_loop, so compile time stays flat in m and the ladder kernel
    does not inline m iterations into each of its ~nbits*(1+1/w)
    multiplies.  Digit a_i is read from lane 0 of a copy of a rotated
    once per iteration (Mosaic has no dynamic lane slice).
    """
    tb, m = a.shape
    n0p = np.uint32(n0p)
    lane0 = jax.lax.broadcasted_iota(jnp.int32, (tb, m + 1), 1) == 0
    z1 = jnp.zeros((tb, 1), U32)

    def body(_, carry):
        acc, a_rot = carry
        prod = a_rot[:, 0:1] * b                  # exact uint32 products
        acc = (acc + jnp.concatenate([prod & DMASK, z1], axis=1)
               + jnp.concatenate([z1, prod >> DBITS], axis=1))
        u = ((acc[:, 0:1] & DMASK) * n0p) & DMASK
        prod2 = u * n                             # (TB, m), exact uint32
        acc = (acc + jnp.concatenate([prod2 & DMASK, z1], axis=1)
               + jnp.concatenate([z1, prod2 >> DBITS], axis=1))
        # digit 0 is now 0 mod B: shift down, carrying its high part
        c0 = acc[:, 0:1] >> DBITS
        acc = (jnp.concatenate([acc[:, 1:], z1], axis=1)
               + jnp.where(lane0, c0, np.uint32(0)))
        return acc, rotate_down(a_rot)

    acc, _ = jax.lax.fori_loop(0, m, body, (jnp.zeros((tb, m + 1), U32), a))
    return acc


def mont_mul_block(a, b, n, n0p):
    """Full normalized Montgomery product on (TB, m) blocks (loop CIOS +
    carry resolve + branch-free conditional subtract) -- the multiply
    the fused ladder kernel composes ~nbits*(1+1/w) times per launch."""
    acc = cios_iterations(a, b, n, n0p)
    return cond_subtract(normalize_static(acc), n)


def make_mont_kernel(m: int, n0p: int):
    """Kernel body specialized to a modulus width m and constant n0p."""

    def mont_mul_kernel(a_ref, b_ref, n_ref, out_ref):
        a = a_ref[...]                            # (TB, m) digits < 2**16
        b = b_ref[...]
        n = n_ref[...]                            # (1, m) modulus digits
        out_ref[...] = mont_mul_block(a, b, n, n0p)

    return mont_mul_kernel


def select_window(table, wins):
    """Branch-free table lookup: table[d] per lane for d = wins[:, 0],
    as a chain of 2**w selects over the (TB, m) power-table entries (the
    window value steers data, never control flow)."""
    d = wins[:, 0:1]                              # (TB, 1)
    out = table[0]
    for k in range(1, len(table)):
        out = jnp.where(d == k, table[k], out)
    return out


def ladder_live_arrays(window: int) -> int:
    """Live (TB, ~m) uint32 arrays in the fused ladder kernel: the
    2**w-row power table dominates, plus the same ~12 CIOS/normalize
    temps as the single-multiply kernel.  Sizes the batch tile."""
    return (1 << window) + LIVE_U32_ARRAYS


def make_ladder_kernel(m: int, n0p: int, window: int, nwin: int):
    """Fused full-ladder windowed modexp kernel body.

    One program owns a (TB, m) residue block and runs the ENTIRE k-ary
    exponentiation there -- to-Montgomery transform, 2**w-entry power
    table build, all nwin windows (w squarings + one branch-free one-hot
    table select + multiply each), and the from-Montgomery exit -- so a
    modexp is ONE kernel launch instead of two per exponent bit, and the
    residue/modulus/table never leave VMEM.  Per-lane exponents arrive
    as a (TB, nwin) array of window values (MSB-first, each < 2**w);
    they only ever feed the one-hot select masks, never control flow,
    so the ladder is constant-time in structure.  w, nwin, m, n0p are
    all baked (one specialization per modulus/exponent geometry)."""
    nt = 1 << window

    def ladder_kernel(base_ref, win_ref, n_ref, r2_ref, one_ref, out_ref):
        base = base_ref[...]                      # (TB, m) digits < 2**16
        wins = win_ref[...]                       # (TB, nwin) window values
        n = n_ref[...]                            # (1, m) modulus digits

        def mm(x, y):
            return mont_mul_block(x, y, n, n0p)

        x = mm(base, jnp.broadcast_to(r2_ref[...], base.shape))   # to Mont
        table = [jnp.broadcast_to(one_ref[...], base.shape), x]
        for _ in range(2, nt):
            table.append(mm(table[-1], x))

        def win_step(_, carry):
            res, w_rot = carry
            for _ in range(window):
                res = mm(res, res)
            return mm(res, select_window(table, w_rot)), rotate_down(w_rot)

        res, _ = jax.lax.fori_loop(
            1, nwin, win_step,
            (select_window(table, wins), rotate_down(wins)))
        plain_one = (jax.lax.broadcasted_iota(U32, (1, m), 1) == 0)
        out_ref[...] = mm(res, jnp.broadcast_to(plain_one.astype(U32),
                                                base.shape))      # exit Mont

    return ladder_kernel


@functools.lru_cache(maxsize=64)
def make_ladder_call(batch_tile: int, m: int, grid: int, n0p: int,
                     window: int, nwin: int, interpret: bool):
    """pallas_call for the fused full-ladder windowed modexp.

    Inputs: base (grid*TB, m), window values (grid*TB, nwin), and the
    (1, m) modulus / R^2 / R-mod-n rows broadcast to every program.
    Output: (grid*TB, m) digits of base**e mod n.
    """
    return pl.pallas_call(
        make_ladder_kernel(m, n0p, window, nwin),
        # no name=: it would rename the HLO instruction (_ladder_call)
        # that the benchmark's kernel metric keys on; kernel_name stays
        # the function's name, ladder_kernel
        grid=(grid,),
        in_specs=[pl.BlockSpec((batch_tile, m), lambda i: (i, 0)),
                  pl.BlockSpec((batch_tile, nwin), lambda i: (i, 0)),
                  pl.BlockSpec((1, m), lambda i: (0, 0)),
                  pl.BlockSpec((1, m), lambda i: (0, 0)),
                  pl.BlockSpec((1, m), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((batch_tile, m), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((grid * batch_tile, m), U32),
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# fused Barrett multiply (the even-modulus twin of the CIOS block)
# ---------------------------------------------------------------------------

# The Barrett block's full products keep ~2m-wide column temps live on
# top of the CIOS-style working set, so its tile budget counts them.
BARRETT_LIVE_U32_ARRAYS = 20


def full_mul_columns(a, b):
    """Lazy full product on blocks: a (TB, ma) x b (TB|1, mb) ->
    (TB, ma+mb) deferred-carry columns, each digit < 2*ma*2**16.

    The schoolbook column accumulation of kernels/dot_mul, restated as a
    lax.fori_loop over a's digits so the fused Barrett ladder (three of
    these per modular multiply, ~nbits*(1+1/w) multiplies per launch)
    traces one body instead of inlining ma iterations everywhere.

    Iteration i adds row a_i*b to an (mb+1)-digit window whose lane 0 is
    column i; column i is then complete, so it is shifted into the top
    of ``low`` and the window moves down one digit.  After ma steps
    ``low`` holds columns 0..ma-1 in order.  Every slice is static (a_i
    comes from lane 0 of a rotated copy of a): Mosaic lowers neither
    dynamic lane slices nor dynamic_update_slice."""
    tb, ma = a.shape
    mb = b.shape[1]
    zeros1 = jnp.zeros((tb, 1), U32)

    def body(_, carry):
        acc, low, a_rot = carry
        prod = a_rot[:, 0:1] * b                  # exact uint32 products
        acc = (acc + jnp.concatenate([prod & DMASK, zeros1], axis=1)
               + jnp.concatenate([zeros1, prod >> DBITS], axis=1))
        low = jnp.concatenate([low[:, 1:], acc[:, 0:1]], axis=1)
        acc = jnp.concatenate([acc[:, 1:], zeros1], axis=1)
        return acc, low, rotate_down(a_rot)

    acc, low, _ = jax.lax.fori_loop(
        0, ma, body,
        (jnp.zeros((tb, mb + 1), U32), jnp.zeros((tb, ma), U32), a))
    return jnp.concatenate([low, acc[:, :mb]], axis=1)


def cond_sub_ge(r, n):
    """Width-preserving branch-free conditional subtract: r if r < n
    else r - n, for r (TB, mw) normalized and n (1, mw).  Same radix-
    complement trick as cond_subtract, keeping all mw digits (Barrett's
    r < 3n needs m+1 digits until the final correction lands)."""
    tb, mw = r.shape
    s = add_lane0(r + (DMASK - n), np.uint32(1))  # lazy, <= 2**17 + 1
    ext = jnp.concatenate([s, jnp.zeros((tb, 1), U32)], axis=1)
    sn = normalize_static(ext, bound=1 << 17)     # (TB, mw+1)
    ge = sn[:, mw:mw + 1]                         # carry out: 1 iff r >= n
    return jnp.where(ge == 1, sn[:, :mw], r)


def barrett_mul_block(a, b, n, mu):
    """Full Barrett modular product on (TB, m) blocks: a*b mod n with
    NO Montgomery form -- the only in-kernel multiply that serves even
    moduli.  Mirrors core/modular._barrett_reduce digit for digit:

      x = a*b                                  (full product, 2m digits)
      t = floor(x / B**(m-1))                  (static slice)
      q_hat = floor(t * mu / B**(m+1))         (truncated mu-multiply)
      r = x - q_hat*n  mod B**(m+1)            (radix-complement, exact
                                                since 0 <= x - q_hat*n
                                                < 3n < B**(m+1))
      two branch-free conditional subtracts    (q_hat >= q - 2)

    n: (1, m) and mu: (1, m+2) ride in as runtime rows (NOT baked), so
    one compiled kernel serves every same-width modulus."""
    tb, m = a.shape
    x = normalize_static(full_mul_columns(a, b),
                         bound=(2 * m) << 16)     # (TB, 2m), a*b exact
    t = x[:, m - 1:]                              # (TB, m+1)
    q_full = normalize_static(full_mul_columns(t, mu),
                              bound=(2 * (m + 1)) << 16)
    q = q_full[:, m + 1:2 * m + 2]                # (TB, m+1) q_hat
    p = normalize_static(full_mul_columns(q, n),
                         bound=(2 * (m + 1)) << 16)  # q_hat*n <= x < B**2m
    # r = x - p on m+1 digits: exact mod B**(m+1) because 0 <= x-p < 3n
    s = add_lane0(x[:, :m + 1] + (DMASK - p[:, :m + 1]), np.uint32(1))
    r = normalize_static(s, bound=1 << 17)        # carry past top drops
    n_ext = jnp.concatenate([n, jnp.zeros((1, 1), U32)], axis=1)
    r = cond_sub_ge(r, n_ext)
    r = cond_sub_ge(r, n_ext)
    return r[:, :m]


def make_barrett_kernel(m: int):
    """Single fused Barrett multiply kernel body (modulus width baked;
    the modulus itself arrives as runtime rows)."""

    def barrett_mul_kernel(a_ref, b_ref, n_ref, mu_ref, out_ref):
        out_ref[...] = barrett_mul_block(
            a_ref[...], b_ref[...], n_ref[...], mu_ref[...])

    return barrett_mul_kernel


def barrett_live_arrays(window: int) -> int:
    """Live (TB, ~m) uint32 arrays in the fused Barrett ladder: the
    2**w-row power table plus the Barrett block's double-width temps."""
    return (1 << window) + BARRETT_LIVE_U32_ARRAYS


def make_barrett_ladder_kernel(m: int, window: int, nwin: int):
    """Fused full-ladder windowed modexp on plain residues via Barrett
    reduction: same one-launch schedule as make_ladder_kernel (power
    table build, w squarings + one-hot select per window) minus the
    Montgomery entry/exit -- Barrett's identity is the literal digit 1,
    so even moduli get the single-launch ladder too."""
    nt = 1 << window

    def ladder_kernel(base_ref, win_ref, n_ref, mu_ref, out_ref):
        base = base_ref[...]                      # (TB, m) residues < n
        wins = win_ref[...]                       # (TB, nwin) window values
        n = n_ref[...]                            # (1, m) modulus digits
        mu = mu_ref[...]                          # (1, m+2) mu digits

        def mm(x, y):
            return barrett_mul_block(x, y, n, mu)

        one = (jax.lax.broadcasted_iota(U32, (1, m), 1) == 0).astype(U32)
        table = [jnp.broadcast_to(one, base.shape), base]
        for _ in range(2, nt):
            table.append(mm(table[-1], base))

        def win_step(_, carry):
            res, w_rot = carry
            for _ in range(window):
                res = mm(res, res)
            return mm(res, select_window(table, w_rot)), rotate_down(w_rot)

        out_ref[...], _ = jax.lax.fori_loop(
            1, nwin, win_step,
            (select_window(table, wins), rotate_down(wins)))

    return ladder_kernel


@functools.lru_cache(maxsize=64)
def make_barrett_call(batch_tile: int, m: int, grid: int, interpret: bool):
    """pallas_call for the fused Barrett multiply.  Inputs: a, b
    (grid*TB, m) digit arrays plus (1, m) modulus and (1, m+2) mu rows
    broadcast to every program (runtime operands: the cache key is
    geometry only, one compilation per width)."""
    return pl.pallas_call(
        make_barrett_kernel(m),
        name="dot_modmul_barrett",
        grid=(grid,),
        in_specs=[pl.BlockSpec((batch_tile, m), lambda i: (i, 0)),
                  pl.BlockSpec((batch_tile, m), lambda i: (i, 0)),
                  pl.BlockSpec((1, m), lambda i: (0, 0)),
                  pl.BlockSpec((1, m + 2), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((batch_tile, m), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((grid * batch_tile, m), U32),
        interpret=interpret,
    )


@functools.lru_cache(maxsize=64)
def make_barrett_ladder_call(batch_tile: int, m: int, grid: int,
                             window: int, nwin: int, interpret: bool):
    """pallas_call for the fused Barrett full-ladder windowed modexp.
    Inputs: base (grid*TB, m), window values (grid*TB, nwin), and the
    (1, m) / (1, m+2) modulus and mu rows."""
    return pl.pallas_call(
        make_barrett_ladder_kernel(m, window, nwin),
        name="dot_modmul_barrett_ladder",
        grid=(grid,),
        in_specs=[pl.BlockSpec((batch_tile, m), lambda i: (i, 0)),
                  pl.BlockSpec((batch_tile, nwin), lambda i: (i, 0)),
                  pl.BlockSpec((1, m), lambda i: (0, 0)),
                  pl.BlockSpec((1, m + 2), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((batch_tile, m), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((grid * batch_tile, m), U32),
        interpret=interpret,
    )


@functools.lru_cache(maxsize=64)
def make_call(batch_tile: int, m: int, grid: int, n0p: int,
              interpret: bool):
    """pallas_call for the fused Montgomery multiply.

    Inputs: a, b (grid*TB, m) digit arrays and the (1, m) modulus block
    (broadcast to every program).  Output: (grid*TB, m) digits < n.
    """
    return pl.pallas_call(
        make_mont_kernel(m, n0p),
        name="dot_modmul_mont",
        grid=(grid,),
        in_specs=[pl.BlockSpec((batch_tile, m), lambda i: (i, 0)),
                  pl.BlockSpec((batch_tile, m), lambda i: (i, 0)),
                  pl.BlockSpec((1, m), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((batch_tile, m), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((grid * batch_tile, m), U32),
        interpret=interpret,
    )
