"""Jit'd wrappers for the fused NTT multiply kernel + CRT recombination.

Entry points follow the kernel-family conventions (interpret mode
auto-selected on CPU, batch padded to the tile and trimmed, tile chosen
outside jit).  The pipeline per multiply:

  split to radix-2**16 digits, zero-pad to N = next_pow2(2 * ndigits)
  one fused kernel launch PER PRIME  ->  residue arrays mod p_i
  one crt_combine kernel launch: Garner mixed-radix CRT (elementwise
  Montgomery ops), digit-column accumulation and ONE deferred-carry
  resolve (kernels/common/carry.normalize_static), in VMEM per tile

Prime count: 2 primes give a CRT modulus ~2**56 -- exact for operands to
~2**24 digits (hundreds of megabits), far past the 64K-bit design point;
3 primes (~2**86) are kept selectable for validation and future wider
digit radices.  ``_resolve_nprimes`` enforces the coefficient bound
``ndigits * (2**16 - 1)**2 < prod(primes)`` at trace time either way.

Garner with ascending primes p1 < p2 < p3 never needs a residue
pre-reduction (r1 < p1 < p2, t2 < p2 < p3), and its mixed-radix digits
(v = r1 + p1*t2 + p1*p2*t3) decompose into 16-bit half products against
the HOST-known constant digits of p1 and p1*p2 -- every partial fits
uint32, lazily accumulated into product columns with a worst case of 26
terms per column (< 2**21, ``kernel.CRT_COLUMN_TERMS``) before the
single static carry resolve.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common import autotune, tiling
from repro.kernels.common.runtime import auto_interpret as _auto_interpret
from repro.kernels.ntt_mul import kernel as K
from repro.resilience import inject as _inject

U32 = jnp.uint32
R = 1 << K.R_BITS
DIGIT_BITS = K.DIGIT_BITS
DMASK = K.DMASK


def next_pow2(x: int) -> int:
    n = 1
    while n < x:
        n *= 2
    return n


def coefficient_bound(ndigits: int) -> int:
    """Max product-polynomial coefficient: ndigits digit pairs, each
    < (2**16 - 1)**2."""
    return ndigits * (DMASK.item() ** 2)


def _resolve_nprimes(ndigits: int, nprimes: int | None) -> int:
    """Validate/choose the CRT prime-set size for an operand width."""
    if nprimes is None:
        from repro.configs.dot_bignum import MUL_DISPATCH
        nprimes = MUL_DISPATCH.ntt_primes
    if nprimes not in (2, 3):
        raise ValueError(f"nprimes must be 2 or 3, got {nprimes!r}")
    m = 1
    for p in K.PRIMES[:nprimes]:
        m *= p
    if coefficient_bound(ndigits) >= m:
        raise ValueError(
            f"{ndigits} digits overflow the {nprimes}-prime CRT modulus "
            f"(need prod(primes) > ndigits * (2**16-1)**2)")
    return nprimes


# ---------------------------------------------------------------------------
# Host-side twiddle tables (cached per (prime, N); Montgomery domain).
# ---------------------------------------------------------------------------

def twiddle_tables(p: int, n: int) -> tuple[np.ndarray, np.ndarray]:
    """(forward, inverse) twiddles, each (log2 N, N//2) uint32, w*R mod p.

    Forward stage s (DIF, half-size N >> (s+1)) uses powers of
    w_m = w**(N/m) with m the stage's block size; inverse stage s (DIT,
    half-size 2**s) uses powers of w_m**-1.  Rows are front-filled and
    zero-padded; lane_twiddles spreads them into the layout the kernel
    reads.
    """
    w = pow(K.GENERATOR, (p - 1) // n, p)
    winv = pow(w, -1, p)
    stages = n.bit_length() - 1
    wf = np.zeros((stages, max(1, n // 2)), np.uint32)
    wi = np.zeros((stages, max(1, n // 2)), np.uint32)
    for s in range(stages):
        for tbl, root, ln in ((wf, w, n >> (s + 1)), (wi, winv, 1 << s)):
            wm = pow(root, n // (2 * ln), p)
            cur = 1
            for j in range(ln):
                tbl[s, j] = cur * R % p
                cur = cur * wm % p
    return wf, wi


@functools.lru_cache(maxsize=64)
def lane_twiddles(p: int, n: int) -> tuple[np.ndarray, np.ndarray]:
    """twiddle_tables spread over the transform's N lanes, the layout the
    kernel reads: row s holds the stage's twiddle w^(k mod half) at lane
    k, so a butterfly finds its factor in its own lane.  Cached per
    (prime, N): the tables are reused by every launch of that width."""
    lanes = np.arange(n)
    return tuple(
        np.stack([tbl[s, lanes % half] for s, half in enumerate(halves)])
        for tbl, halves in zip(
            twiddle_tables(p, n),
            ([n >> (s + 1) for s in range(n.bit_length() - 1)],
             [1 << s for s in range(n.bit_length() - 1)])))


# ---------------------------------------------------------------------------
# Prepared operands: the forward NTT of a FIXED operand is a
# precomputation exactly like twiddles (van der Hoeven & Lecerf), so the
# repeat-multiply-by-a-constant consumers (Newton reciprocal levels,
# divmod_const, Barrett's mu and n, base-conversion chunk constants)
# never pay for the same transform twice.  Cached host-side in a bounded
# LRU keyed by (value, prime set, N) with hit/miss/eviction counters
# (repro.api.cache_stats); capacity via configure(ntt_cache_entries=...),
# 0 disables the prepared path entirely (the A/B switch benchmarks use).
# ---------------------------------------------------------------------------

DEFAULT_CACHE_ENTRIES = 64

_prepared_cache: "collections.OrderedDict[tuple, tuple]" = \
    collections.OrderedDict()
_prepared_counters = {"hits": 0, "misses": 0, "evictions": 0}


def operand_cache_capacity() -> int:
    """LRU entry cap for the prepared-operand cache (0: path disabled)."""
    from repro import config as _rc
    cap = _rc.resolve("ntt_cache_entries")
    if cap is None:
        return DEFAULT_CACHE_ENTRIES
    cap = int(cap)
    if cap < 0:
        raise ValueError(f"ntt_cache_entries must be >= 0, got {cap}")
    return cap


def operand_cache_stats() -> dict:
    """Counters + occupancy for repro.api.cache_stats()."""
    return dict(_prepared_counters,
                entries=len(_prepared_cache),
                capacity=operand_cache_capacity())


def clear_operand_cache() -> None:
    _prepared_cache.clear()
    for k in _prepared_counters:
        _prepared_counters[k] = 0


def _host_ntt_forward(digits: np.ndarray, p: int) -> np.ndarray:
    """Exact uint64 replica of the kernel's DIF forward transform for one
    (N,) natural-order digit vector mod p (output order bit-reversed,
    NORMAL domain -- matching what ntt_forward leaves for the pointwise
    product).  p < 2**30, so every (u + p - v) % p * tw product stays
    below 2**60: exact in uint64."""
    n = digits.shape[-1]
    x = digits.astype(np.uint64) % p
    w = pow(K.GENERATOR, (p - 1) // n, p)
    for s in range(n.bit_length() - 1):
        ln = n >> (s + 1)
        wm = pow(w, n // (2 * ln), p)
        tw = np.empty((ln,), np.uint64)
        cur = 1
        for j in range(ln):
            tw[j] = cur
            cur = cur * wm % p
        y = x.reshape(-1, 2, ln)
        u, v = y[:, 0, :], y[:, 1, :]
        x = np.stack([(u + v) % p, (u + p - v) % p * tw % p],
                     axis=1).reshape(n)
    return x.astype(np.uint32)


def prepared_operand(value: int, n: int, nprimes: int) -> tuple:
    """Per-prime (1, N) forward-NTT rows of a host-known operand value,
    served from the bounded LRU (key: (value, prime set, N) -- same
    value at a different transform length or prime count is a distinct
    entry, so two moduli never share a prepared operand)."""
    key = (value, nprimes, n)
    hit = _prepared_cache.get(key)
    if hit is not None:
        _prepared_cache.move_to_end(key)
        _prepared_counters["hits"] += 1
        return hit
    _prepared_counters["misses"] += 1
    digits = np.array([(value >> (DIGIT_BITS * k)) & 0xFFFF
                       for k in range(n)], np.uint32)
    # the rows MUST be concrete arrays: a caller may hit this miss path
    # while inside an outer jit trace, and without the eager guard the
    # [None, :] below would stage and poison the process-global cache
    # with that trace's tracers (crashing every later caller)
    with jax.ensure_compile_time_eval():
        rows = tuple(jnp.asarray(_host_ntt_forward(digits, p)[None, :])
                     for p in K.PRIMES[:nprimes])
    _prepared_cache[key] = rows
    cap = operand_cache_capacity()
    while len(_prepared_cache) > max(1, cap):
        _prepared_cache.popitem(last=False)
        _prepared_counters["evictions"] += 1
    return rows


# ---------------------------------------------------------------------------
# CRT recombination (the crt_combine kernel).
# ---------------------------------------------------------------------------

def _crt_tile(out_digits: int, rows: int) -> int:
    tb = tiling.batch_tile(
        out_digits, rows, budget=tiling.budget_words(K.CRT_LIVE_U32_ARRAYS),
        max_tile=K.MAX_TILE)
    return min(tb, rows)          # a block of fewer rows spans them all


def crt_combine(residues, out_digits: int, interpret=None):
    """Per-prime residue arrays (rows, >= out_digits) -> (rows,
    out_digits) normalized radix-2**16 digits of the recombined
    coefficients, mod 2**(16 * out_digits): one ``crt_combine`` kernel
    launch (Garner and the carry resolve in one VMEM pass per tile)."""
    rows, n = residues[0].shape
    interpret = _auto_interpret(interpret)
    return K.make_crt_call(_crt_tile(out_digits, rows), rows, n, out_digits,
                           len(residues), interpret)(*residues)


# ---------------------------------------------------------------------------
# Entry points.
# ---------------------------------------------------------------------------

def _heuristic_tile(n: int, batch: int) -> int:
    return tiling.batch_tile(
        n, batch, budget=tiling.budget_words(K.LIVE_U32_ARRAYS),
        max_tile=K.MAX_TILE)


@functools.partial(jax.jit,
                   static_argnames=("nprimes", "tb", "interpret"))
def _call(a_d, b_d, twiddles, nprimes: int, tb: int, interpret: bool):
    batch, nd = a_d.shape
    n = next_pow2(2 * nd)
    pad_b = (-batch) % tb
    a_p = jnp.pad(a_d, ((0, pad_b), (0, n - nd)))
    b_p = jnp.pad(b_d, ((0, pad_b), (0, n - nd)))
    grid = a_p.shape[0] // tb
    residues = [K.make_call(tb, n, grid, p, interpret)(a_p, b_p, wf, wi)
                for p, (wf, wi) in zip(K.PRIMES[:nprimes], twiddles)]
    return crt_combine(residues, 2 * nd, interpret)[:batch]


def ntt_mul_digits(a_digits, b_digits, nprimes: int | None = None,
                   interpret=None):
    """(batch, nd) uint32 radix-2**16 digits x2 -> (batch, 2*nd) digits
    of the full product (one fused NTT launch per CRT prime)."""
    a = jnp.asarray(a_digits, U32)
    b = jnp.asarray(b_digits, U32)
    batch, nd = a.shape
    assert b.shape == a.shape
    nprimes = _resolve_nprimes(nd, nprimes)
    interpret = _auto_interpret(interpret)
    n = next_pow2(2 * nd)
    twiddles = tuple(
        tuple(jnp.asarray(t) for t in lane_twiddles(p, n))
        for p in K.PRIMES[:nprimes])
    tb = autotune.pick_tile(
        "ntt_mul", (n, batch, DIGIT_BITS, nprimes, interpret),
        _heuristic_tile(n, batch), batch,
        run=lambda t: _call(a, b, twiddles, nprimes, t, interpret),
        max_tile=K.MAX_TILE)
    return _call(a, b, twiddles, nprimes, tb, interpret)


def ntt_mul_limbs32(a_limbs, b_limbs, nprimes: int | None = None,
                    interpret=None):
    """(batch, m) uint32 saturated limbs x2 -> (batch, 2m) limbs (full
    product), radix-converted at entry/exit (paper sec 3.3)."""
    _inject.fire("kernels/ntt_mul")
    from repro.core import mul as coremul
    m = a_limbs.shape[-1]
    a_d = coremul.split_digits(jnp.asarray(a_limbs, U32), DIGIT_BITS)
    b_d = coremul.split_digits(jnp.asarray(b_limbs, U32), DIGIT_BITS)
    p_d = ntt_mul_digits(a_d, b_d, nprimes, interpret)
    return coremul.join_digits(p_d, DIGIT_BITS, 2 * m)


@functools.partial(jax.jit, static_argnames=("nprimes", "tb", "interpret"))
def _call_prepared(a_d, fb_rows, twiddles, nprimes: int, tb: int,
                   interpret: bool):
    batch, nd = a_d.shape
    n = next_pow2(2 * nd)
    pad_b = (-batch) % tb
    a_p = jnp.pad(a_d, ((0, pad_b), (0, n - nd)))
    grid = a_p.shape[0] // tb
    residues = [
        K.make_prepared_call(tb, n, grid, p, interpret)(a_p, fb, wf, wi)
        for p, fb, (wf, wi) in zip(K.PRIMES[:nprimes], fb_rows, twiddles)]
    return crt_combine(residues, 2 * nd, interpret)[:batch]


def ntt_mul_digits_prepared(a_digits, b_value: int,
                            nprimes: int | None = None, interpret=None):
    """(batch, nd) digits x a HOST-KNOWN operand value -> (batch, 2*nd)
    full-product digits, with b's forward transforms served from the
    prepared-operand cache -- each launch runs ONE forward transform
    instead of two.  ``b_value`` must equal the value the caller would
    otherwise pass as a (nd,) digit array (< 2**(16*nd)); the prepared
    rows are runtime (1, N) inputs, so repeat calls share one trace."""
    a = jnp.asarray(a_digits, U32)
    batch, nd = a.shape
    b_value = int(b_value)
    assert 0 <= b_value < 1 << (DIGIT_BITS * nd), \
        "prepared operand wider than the digit array it replaces"
    nprimes = _resolve_nprimes(nd, nprimes)
    interpret = _auto_interpret(interpret)
    n = next_pow2(2 * nd)
    twiddles = tuple(
        tuple(jnp.asarray(t) for t in lane_twiddles(p, n))
        for p in K.PRIMES[:nprimes])
    fb_rows = prepared_operand(b_value, n, nprimes)
    tb = autotune.pick_tile(
        "ntt_mul_prepared", (n, batch, DIGIT_BITS, nprimes, interpret),
        _heuristic_tile(n, batch), batch,
        run=lambda t: _call_prepared(a, fb_rows, twiddles, nprimes, t,
                                     interpret),
        max_tile=K.MAX_TILE)
    return _call_prepared(a, fb_rows, twiddles, nprimes, tb, interpret)


def ntt_mul_limbs32_prepared(a_limbs, b_value: int,
                             nprimes: int | None = None, interpret=None):
    """32-bit limb twin of ntt_mul_digits_prepared: (batch, m) limbs x a
    host-known value < 2**(32m) -> (batch, 2m) limbs."""
    _inject.fire("kernels/ntt_mul")
    from repro.core import mul as coremul
    m = a_limbs.shape[-1]
    a_d = coremul.split_digits(jnp.asarray(a_limbs, U32), DIGIT_BITS)
    p_d = ntt_mul_digits_prepared(a_d, b_value, nprimes, interpret)
    return coremul.join_digits(p_d, DIGIT_BITS, 2 * m)
