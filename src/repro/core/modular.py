"""Batched Montgomery modular arithmetic over radix-2**16 digits.

The crypto-serving substrate (paper sec 4.4/4.5: DoTSSL): RSA / DH / DSA
reduce to modular exponentiation, which reduces to Montgomery multiply.
On TPU the SIMD win is the batch axis -- thousands of independent modexps
vectorized over VPU lanes -- while each CIOS iteration uses the same
deferred-carry structure as DoT (lazy uint32 digits, one carry-resolve
pass at the end) instead of per-step carry propagation.

Lazy-digit overflow analysis (why no per-iteration normalization):
  each CIOS iteration adds <= 4*(B-1) + carry < 5*2**16 to any digit, so
  after m iterations digits < 5*m*2**16 -- safe in uint32 for m <= 2**13
  (operands up to 128 Kbit, far beyond RSA sizes).

Exponentiation is a constant-time fixed-window (k-ary) ladder shared by
every device backend (_windowed_ladder): a 2**w-entry power table, w
squarings + one branch-free table gather per window -- ~nbits*(1 + 1/w)
+ 2**w modular multiplies instead of the bit-serial ladder's ~2*nbits,
with no data-dependent branching on exponent bits (matching how crypto
libraries avoid key-dependent timing).  On the "pallas" backend the
WHOLE ladder is one fused kernel launch (kernels/dot_modmul): residue,
modulus, and power table stay VMEM-resident across all steps.

Backend dispatch
----------------
Every public op takes ``backend`` (default: the module default, "jnp"):

  * ``reference`` -- host-side Python-int oracle (exact, slow; the
    ground truth every other backend is tested against),
  * ``jnp``       -- the pure-jnp formulation below (HBM round-trips the
    accumulator every CIOS scan step),
  * ``pallas``    -- the fused VMEM-resident kernel in
    kernels/dot_modmul (interpret mode on CPU, tiled on TPU),
  * ``barrett``   -- Barrett reduction (Mathemagix-style): precomputed
    mu = floor(B**2m / n), reduction = two pipeline multiplies + a
    bounded correction.  No Montgomery form, no parity restriction --
    handles EVEN moduli.  Montgomery setup rejects even n with a
    pointer here; mod_mul/mod_exp auto-route a BarrettCtx to a Barrett
    backend,
  * ``barrett_fused`` -- the same Barrett schedule as ONE fused Pallas
    launch per multiply / per FULL modexp ladder
    (kernels/dot_modmul's Barrett block: mul -> truncated mu-multiply
    -> q*n subtract -> two branch-free corrections, everything
    VMEM-resident) -- even moduli get the single-launch ladder too.

core/rsa.py, examples/rsa_crypto.py and benchmarks/bench_crypto.py all
route through this one API, so backends can be compared head-to-head.

Per-lane moduli
---------------
A ``MontLanes`` context holds one odd modulus per lane, all of one width:
lane i of mont_mul / mod_mul / mod_exp then works modulo its own n_i.
The reference, jnp and pallas backends take it (the Montgomery kernels
take every modulus and its constants as run-time operands); the Barrett
backends, which take one modulus a batch, refuse it.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import limbs as L
from repro.core.mul import normalize_digits, normalize_digits_scan

U32 = jnp.uint32
DIGIT_BITS = 16
BASE = 1 << DIGIT_BITS
MASK = jnp.uint32(BASE - 1)

BACKENDS = ("reference", "jnp", "pallas", "barrett", "barrett_fused")
_DEFAULT_BACKEND = "jnp"


def set_default_backend(name: str) -> None:
    """Set the module-wide default backend for all modular ops."""
    global _DEFAULT_BACKEND
    if name not in BACKENDS:
        raise ValueError(f"unknown backend {name!r}; choose from {BACKENDS}")
    _DEFAULT_BACKEND = name


def get_default_backend() -> str:
    return _DEFAULT_BACKEND


def _resolve_backend(backend: str | None, ctx=None) -> str:
    backend = backend or _DEFAULT_BACKEND
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; choose from {BACKENDS}")
    # Even moduli carry a BarrettCtx; the Montgomery backends cannot
    # serve them, so auto-route to Barrett instead of failing deep in
    # a kernel: the fused Barrett kernel for "pallas" (the user asked
    # for a kernel), the jnp composition for "jnp".  The "reference"
    # oracle handles any parity and is kept.
    if isinstance(ctx, BarrettCtx):
        if backend == "pallas":
            return "barrett_fused"
        if backend == "jnp":
            return "barrett"
    if isinstance(ctx, MontLanes) and backend in ("barrett",
                                                  "barrett_fused"):
        raise ValueError(_LANES_NOT_BARRETT)
    return backend


@dataclasses.dataclass(frozen=True)
class MontCtx:
    """Host-side Montgomery context for an odd modulus n (R = B**m)."""
    m: int                       # digits
    n: int                       # python int modulus
    n0p: int                     # -n^{-1} mod B
    n_digits: np.ndarray         # (m,)
    r2_digits: np.ndarray        # R^2 mod n   (to enter Montgomery form)
    one_digits: np.ndarray       # R mod n     (Montgomery form of 1)


@dataclasses.dataclass(frozen=True)
class MontLanes:
    """Montgomery constants per lane: lane i of a batch works modulo its
    own odd n_i, every n_i of width m digits (R = B**m for all lanes).
    The arrays may be traced values, so a program can gather its lanes'
    rows from a key table on the device (serve/bignum_engine)."""
    m: int                       # digits
    n_digits: jax.Array          # (N, m) each lane's modulus
    r2_digits: jax.Array         # (N, m) R^2 mod n_i
    n0p: jax.Array               # (N,) uint32 -n_i^{-1} mod B


_LANES_NOT_BARRETT = (
    "a per-lane Montgomery context (MontLanes) runs on the reference, jnp "
    "and pallas backends; the Barrett backends take one modulus a batch")


def mont_lanes(moduli, nbits: int) -> MontLanes:
    """Host-built per-lane context for odd ``moduli`` of one width."""
    ctxs = [mont_setup(int(n), nbits) for n in moduli]
    return MontLanes(
        m=ctxs[0].m,
        n_digits=np.stack([c.n_digits for c in ctxs]),
        r2_digits=np.stack([c.r2_digits for c in ctxs]),
        n0p=np.array([c.n0p for c in ctxs], np.uint32))


def _lane_moduli(ctx, lanes: int) -> list:
    """The python-int modulus of each of ``lanes`` lanes (host values)."""
    if isinstance(ctx, MontLanes):
        rows = np.asarray(ctx.n_digits, np.uint32).reshape(-1, ctx.m)
        return [L.limbs_to_int(r, DIGIT_BITS) for r in rows]
    return [ctx.n] * lanes


@functools.lru_cache(maxsize=128)
def mont_setup(n: int, nbits: int | None = None) -> MontCtx:
    """Host-side Montgomery constants, memoized per (n, nbits): callers
    like RSAKey.ctx rebuild the context on every access, so repeated
    setups (including the R**2 mod n bigint work) must be cache hits.
    The frozen dataclass and its arrays are shared -- treat as read-only.
    """
    if n % 2 == 0 or n <= 2:
        raise ValueError(
            f"Montgomery arithmetic requires an odd modulus > 2, got "
            f"n % 2 == {n % 2}; use barrett_setup / mod_setup (Barrett "
            f"reduction handles even moduli)")
    nbits = nbits or n.bit_length()
    m = -(-nbits // DIGIT_BITS)
    R = 1 << (DIGIT_BITS * m)
    n0p = (-pow(n, -1, BASE)) % BASE
    return MontCtx(
        m=m, n=n, n0p=n0p,
        n_digits=L.int_to_limbs(n, m, DIGIT_BITS),
        r2_digits=L.int_to_limbs((R * R) % n, m, DIGIT_BITS),
        one_digits=L.int_to_limbs(R % n, m, DIGIT_BITS),
    )


@dataclasses.dataclass(frozen=True)
class BarrettCtx:
    """Host-side Barrett context for ANY modulus n >= 2 (even or odd).

    mu = floor(B**2m / n) is the fixed-point reciprocal that turns
    reduction into two multiplies (van der Hoeven & Lecerf's SIMD-
    friendly companion to vectorized multiplication).
    """
    m: int                       # digits
    n: int                       # python int modulus
    mu: int                      # python int mu (host-known: the fixed
    #                              operands feed the prepared-NTT cache)
    n_digits: np.ndarray         # (m,)
    mu_digits: np.ndarray        # (m + 2,): mu = floor(B**2m / n)


@functools.lru_cache(maxsize=128)
def barrett_setup(n: int, nbits: int | None = None) -> BarrettCtx:
    """Memoized like mont_setup: _as_barrett promotes a MontCtx on EVERY
    Barrett-path call, and the B**2m // n bigint division is exactly the
    kind of host work that must not repeat per multiply."""
    if n < 2:
        raise ValueError("Barrett reduction requires a modulus >= 2")
    nbits = nbits or n.bit_length()
    m = -(-nbits // DIGIT_BITS)
    if n < BASE ** (m - 1):
        # the q_hat <= q <= q_hat + 2 bound (and the m+2-digit mu
        # sizing) both need the top declared digit nonzero
        raise ValueError(
            f"barrett_setup: nbits={nbits} over-declares the modulus "
            f"(n has {n.bit_length()} bits); Barrett's trial-quotient "
            f"bound needs the top digit nonzero -- pass nbits <= "
            f"{(-(-n.bit_length() // DIGIT_BITS)) * DIGIT_BITS}")
    mu = (BASE ** (2 * m)) // n
    return BarrettCtx(
        m=m, n=n, mu=mu,
        n_digits=L.int_to_limbs(n, m, DIGIT_BITS),
        mu_digits=L.int_to_limbs(mu, m + 2, DIGIT_BITS),
    )


def mod_setup(n: int, nbits: int | None = None):
    """Context for a modulus of either parity: MontCtx for odd n (the
    fast fused-kernel path), BarrettCtx for even n (auto-routed to the
    Barrett backend by mod_mul / mod_exp)."""
    if n % 2 == 1 and n > 2:
        return mont_setup(n, nbits)
    return barrett_setup(n, nbits)


def _as_barrett(ctx) -> BarrettCtx:
    if isinstance(ctx, BarrettCtx):
        return ctx
    if isinstance(ctx, MontLanes):
        raise ValueError(_LANES_NOT_BARRETT)
    return barrett_setup(ctx.n, ctx.m * DIGIT_BITS)   # memoized setup


def _barrett_reduce(x: jax.Array, ctx: BarrettCtx) -> jax.Array:
    """x mod n for (..., 2m) normalized digits with x < n * B**m
    (anything the product of two residues can produce).

    q_hat = floor(floor(x / B**(m-1)) * mu / B**(m+1)) underestimates
    q = floor(x / n) by at most 2 (classic Barrett bound; n >= B**(m-1)
    holds by construction of m), so r = x - q_hat*n < 3n and a masked
    while-loop finishes in <= 2 trips.  Both multiplies route through
    the autotuned pipeline (core/div.mul_digits_via_pipeline).
    """
    from repro.core import div as DV

    m = ctx.m
    x = jnp.asarray(x, U32)
    mu = jnp.asarray(ctx.mu_digits, U32)
    n_dig = jnp.asarray(ctx.n_digits, U32)

    t = x[..., m - 1:]                                 # floor(x / B**(m-1))
    # mu and n are host-known per context: both multiplies declare their
    # fixed operand so huge moduli hit the prepared-operand NTT cache
    q = DV._mul_equalized(t, mu, DIGIT_BITS,
                          b_const=ctx.mu)[..., m + 1: 2 * m + 2]
    p = DV._mul_equalized(q, n_dig, DIGIT_BITS,
                          b_const=ctx.n)[..., : 2 * m]   # q_hat*n <= x
    r, _ = DV.sub_digits(x, p, DIGIT_BITS)
    r = r[..., : m + 1]                                # r < 3n < B**(m+1)
    n_w = jnp.broadcast_to(DV._pad_to(n_dig, m + 1), r.shape)

    def cond(r):
        return jnp.any(DV.ge_digits(r, n_w, DIGIT_BITS) == 1)

    def body(r):
        over = DV.ge_digits(r, n_w, DIGIT_BITS)
        return DV.sub_digits(r, n_w * over[..., None], DIGIT_BITS)[0]

    r = jax.lax.while_loop(cond, body, r)
    return r[..., :m]


def barrett_mod_mul(a: jax.Array, b: jax.Array, ctx) -> jax.Array:
    """(a * b) mod n on (..., m) digit arrays (no Montgomery form)."""
    from repro.core import div as DV

    bctx = _as_barrett(ctx)
    x = DV._mul_equalized(jnp.asarray(a, U32), jnp.asarray(b, U32),
                          DIGIT_BITS)                  # (..., 2m)
    return _barrett_reduce(x, bctx)


def _barrett_mod_exp(base: jax.Array, exp_bits: jax.Array, ctx,
                     window: int | None = None,
                     unroll: bool = False) -> jax.Array:
    """Windowed constant-time ladder on plain residues (Barrett needs no
    domain transform: table entry 0 is the literal digit 1)."""
    bctx = _as_barrett(ctx)
    x = jnp.asarray(base, U32)
    one = jnp.zeros((bctx.m,), U32).at[0].set(1)
    return _windowed_ladder(
        lambda a, b: barrett_mod_mul(a, b, bctx), one, x, exp_bits,
        window, unroll=unroll)


def _ge(a: jax.Array, b: jax.Array) -> jax.Array:
    """a >= b on normalized digit arrays; returns (...,) bool."""
    # lexicographic from the most significant digit
    gt = a > b
    lt = a < b
    # highest index where digits differ decides
    idx = jnp.arange(a.shape[-1])
    diff = gt.astype(jnp.int32) - lt.astype(jnp.int32)
    # weight by position: the most significant nonzero diff wins
    def step(carry, x):
        d = x
        return jnp.where(d != 0, d, carry), None
    d_t = jnp.moveaxis(diff, -1, 0)
    out, _ = jax.lax.scan(step, jnp.zeros(a.shape[:-1], jnp.int32), d_t)
    return out >= 0


def _sub_mod(a: jax.Array, n_dig: jax.Array) -> jax.Array:
    """a - n on digit arrays (a >= n guaranteed by caller), normalized."""
    mask = MASK
    comp = (mask - n_dig) & mask
    t = a + comp
    t = t.at[..., 0].add(1)
    t = normalize_digits(t, DIGIT_BITS)
    # drop the implicit B**m carry: it lands beyond the array only if a>=n;
    # with equal lengths the carry out of the top digit vanishes mod B**m.
    return t


def _flatten_batch(x: jax.Array, m: int):
    """(..., m) -> ((N, m), batch_shape) for the 2-D kernel entry points."""
    batch_shape = x.shape[:-1]
    return x.reshape((-1, m)), batch_shape


def _mont_mul_jnp(a: jax.Array, b: jax.Array, ctx,
                  lazy: bool = True) -> jax.Array:
    """CIOS Montgomery product: a*b*R^{-1} mod n (pure-jnp backend).

    a, b: (..., m) normalized digits < 2**16, values < n.  ``ctx`` holds
    one modulus (MontCtx) or one per lane (MontLanes, whose rows line up
    with the batch).
    Sequential over the m digits of a (inherent to Montgomery); fully
    vectorized over the batch and the m-digit vector ops per iteration;
    digits stay lazy (deferred carries) across all iterations.

    lazy=False normalizes the accumulator EVERY iteration (the carry-
    chasing structure of non-DoT implementations); the benchmark harness
    uses it as the integration baseline (paper sec 4.4).
    """
    m = ctx.m
    n_dig = jnp.asarray(ctx.n_digits, U32)       # (m,) or (N, m)
    n0p = jnp.asarray(ctx.n0p, U32)              # () or (N,)
    bits = jnp.uint32(DIGIT_BITS)

    a = jnp.asarray(a, U32)
    b = jnp.asarray(b, U32)
    batch_shape = jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1])
    acc0 = jnp.zeros(batch_shape + (m + 1,), U32)

    a_t = jnp.moveaxis(jnp.broadcast_to(a, batch_shape + (m,)), -1, 0)

    def step(acc, ai):
        # acc += a_i * b   (lo into j, hi into j+1) -- lazy adds
        prod = ai[..., None] * b                      # (..., m) exact u32
        lo = prod & MASK
        hi = prod >> bits
        acc = acc.at[..., :m].add(lo)
        acc = acc.at[..., 1:m + 1].add(hi)
        # u = (acc[0] mod B) * n0p mod B ; acc += u * n
        u = ((acc[..., 0] & MASK) * n0p) & MASK
        prod2 = u[..., None] * n_dig
        lo2 = prod2 & MASK
        hi2 = prod2 >> bits
        acc = acc.at[..., :m].add(lo2)
        acc = acc.at[..., 1:m + 1].add(hi2)
        # digit 0 is now 0 mod B; shift down one digit, carrying its high part
        c0 = acc[..., 0] >> bits
        acc = jnp.concatenate(
            [acc[..., 1:], jnp.zeros(batch_shape + (1,), U32)], axis=-1)
        acc = acc.at[..., 0].add(c0)
        if not lazy:
            # non-DoT baseline: resolve every carry immediately (sequential
            # per-digit pass each iteration, like the ADC-chain structure)
            acc = normalize_digits_scan(acc, DIGIT_BITS)
        return acc, None

    acc, _ = jax.lax.scan(step, acc0, a_t)
    acc = normalize_digits(acc, DIGIT_BITS)           # (..., m+1), t < 2n
    # conditional subtract: t >= n -> t - n
    n_ext = jnp.concatenate(
        [n_dig, jnp.zeros(n_dig.shape[:-1] + (1,), U32)], axis=-1)
    ge = _ge(acc, jnp.broadcast_to(n_ext, acc.shape))
    sub = _sub_mod(acc, n_ext)[..., : m + 1]
    out = jnp.where(ge[..., None], sub, acc)
    return out[..., :m]


def _mont_mul_reference(a, b, ctx) -> jax.Array:
    """Host-side Python-int oracle (exact; defines correctness)."""
    from repro.kernels.dot_modmul import ref as _ref
    a = np.asarray(a, np.uint32)
    b = np.asarray(b, np.uint32)
    shape = np.broadcast_shapes(a.shape[:-1], b.shape[:-1]) + (ctx.m,)
    a2, batch_shape = _flatten_batch(np.broadcast_to(a, shape), ctx.m)
    b2, _ = _flatten_batch(np.broadcast_to(b, shape), ctx.m)
    out = _ref.mont_mul_ref(a2, b2, _lane_moduli(ctx, a2.shape[0]))
    return jnp.asarray(out.reshape(batch_shape + (ctx.m,)))


def mont_mul(a: jax.Array, b: jax.Array, ctx: MontCtx, lazy: bool = True,
             backend: str | None = None) -> jax.Array:
    """CIOS Montgomery product a*b*R^{-1} mod n on (..., m) digit arrays,
    dispatched to the selected backend (see module docstring).

    ``lazy`` applies to the jnp backend only: lazy=False is the eager
    per-iteration-normalization measurement baseline (bench_gmp).  The
    pallas kernel is lazy by construction; reference is exact host math.
    """
    backend = _resolve_backend(backend, ctx)
    if backend in ("barrett", "barrett_fused"):
        raise ValueError(
            "mont_mul computes a*b*R^{-1} (Montgomery form); the Barrett "
            "backends have no R -- use mod_mul / mod_exp, which dispatch "
            "to Barrett multiplies on plain residues")
    if backend == "jnp":
        return _mont_mul_jnp(a, b, ctx, lazy)
    if backend == "pallas":
        from repro.kernels.dot_modmul import ops as _mops
        from repro.resilience import guard as _guard
        a = jnp.asarray(a, U32)
        b = jnp.asarray(b, U32)
        shape = jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1]) + (ctx.m,)
        a2, batch_shape = _flatten_batch(jnp.broadcast_to(a, shape), ctx.m)
        b2, _ = _flatten_batch(jnp.broadcast_to(b, shape), ctx.m)
        out = _guard.run("montmul", ctx.m * DIGIT_BITS, [
            ("pallas", lambda: _mops.dot_mont_mul(a2, b2, ctx)),
            ("jnp", lambda: _mont_mul_jnp(a2, b2, ctx, lazy)),
        ])
        return out.reshape(batch_shape + (ctx.m,))
    return _mont_mul_reference(a, b, ctx)


def to_mont(x: jax.Array, ctx: MontCtx,
            backend: str | None = None) -> jax.Array:
    return mont_mul(x, jnp.asarray(ctx.r2_digits, U32), ctx,
                    backend=backend)


def from_mont(x: jax.Array, ctx: MontCtx,
              backend: str | None = None) -> jax.Array:
    one = jnp.zeros((ctx.m,), U32).at[0].set(1)
    return mont_mul(x, one, ctx, backend=backend)


def _mod_mul_reference(a, b, ctx) -> jax.Array:
    """Host-side Python-int (a*b) mod n oracle (any modulus parity)."""
    a = np.asarray(a, np.uint32)
    b = np.asarray(b, np.uint32)
    shape = np.broadcast_shapes(a.shape[:-1], b.shape[:-1]) + (ctx.m,)
    a2, batch_shape = _flatten_batch(np.broadcast_to(a, shape), ctx.m)
    b2, _ = _flatten_batch(np.broadcast_to(b, shape), ctx.m)
    out = np.stack([
        L.int_to_limbs((L.limbs_to_int(a2[i], DIGIT_BITS)
                        * L.limbs_to_int(b2[i], DIGIT_BITS)) % ctx.n,
                       ctx.m, DIGIT_BITS)
        for i in range(a2.shape[0])])
    return jnp.asarray(out.reshape(batch_shape + (ctx.m,)))


def mod_mul(a: jax.Array, b: jax.Array, ctx,
            backend: str | None = None) -> jax.Array:
    """Plain modular product.  Montgomery backends enter/leave Montgomery
    form; the Barrett backend (or any BarrettCtx, e.g. an even modulus
    from mod_setup) multiplies and reduces directly."""
    backend = _resolve_backend(backend, ctx)
    if backend == "barrett":
        return barrett_mod_mul(a, b, ctx)
    if backend == "barrett_fused":
        from repro.kernels.dot_modmul import ops as _mops
        from repro.resilience import guard as _guard
        bctx = _as_barrett(ctx)
        a = jnp.asarray(a, U32)
        b = jnp.asarray(b, U32)
        shape = jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1]) + (bctx.m,)
        a2, batch_shape = _flatten_batch(jnp.broadcast_to(a, shape), bctx.m)
        b2, _ = _flatten_batch(jnp.broadcast_to(b, shape), bctx.m)
        out = _guard.run("modmul", bctx.m * DIGIT_BITS, [
            ("barrett_fused", lambda: _mops.dot_barrett_mul(a2, b2, bctx)),
            ("barrett", lambda: barrett_mod_mul(a2, b2, bctx)),
        ])
        return out.reshape(batch_shape + (bctx.m,))
    if backend == "reference" and isinstance(ctx, BarrettCtx):
        return _mod_mul_reference(a, b, ctx)    # no Montgomery form exists
    return from_mont(
        mont_mul(to_mont(a, ctx, backend), to_mont(b, ctx, backend), ctx,
                 backend=backend), ctx, backend)


def _windowed_ladder(mm, one, x, exp_bits, window: int | None = None,
                     unroll: bool = False) -> jax.Array:
    """The ONE fixed-window (k-ary) constant-time exponentiation schedule
    shared by every device backend (jnp Montgomery, Barrett; the fused
    Pallas kernel runs the same schedule inside one launch).

    ``mm(a, b)`` is the backend's modular multiply on (..., m) digit
    arrays in its own domain; ``one`` is the multiplicative identity in
    that domain (R mod n for Montgomery, the digit 1 for Barrett); ``x``
    is the base already in-domain.  Schedule per ``exp_bits`` (MSB-first
    bits, (nbits,) or (..., nbits)):

      * build the 2**w-entry power table t[j] = x**j (2**w - 2 mults),
      * res := t[window 0]  (branch-free gather -- saves the w identity
        squarings a pad-with-leading-zeros ladder would burn, which is
        also what keeps the multiply count under nbits*(1 + 1/w) + 2**w
        for ALL nbits, not just multiples of w),
      * per remaining window: w squarings, then one multiply by the
        gathered table entry -- square always, multiply always; the
        exponent only ever feeds branch-free gather indices, never
        control flow.

    ``unroll=True`` replaces the lax.scan over windows with a Python
    loop so trace-time mm() calls == runtime modular multiplies (the
    call-counting test + tiny-exponent callers); results are identical.
    """
    from repro.configs.dot_bignum import pick_modexp_window
    from repro.kernels.common.windows import exponent_windows

    eb = jnp.asarray(exp_bits, U32)
    nbits = eb.shape[-1]
    w = int(window if window is not None else pick_modexp_window(nbits))
    if w < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    x = jnp.asarray(x, U32)
    batch_shape = jnp.broadcast_shapes(x.shape[:-1], eb.shape[:-1])
    m = x.shape[-1]
    x = jnp.broadcast_to(x, batch_shape + (m,))
    wv = exponent_windows(
        jnp.broadcast_to(eb, batch_shape + (nbits,)), w)   # (..., nwin)
    nwin = wv.shape[-1]

    table = [jnp.broadcast_to(jnp.asarray(one, U32), x.shape), x]
    for _ in range(2, 1 << w):
        table.append(mm(table[-1], x))
    tab = jnp.stack(table[: 1 << w], axis=-2)              # (..., 2**w, m)

    def select(d):
        idx = d.astype(jnp.int32)[..., None, None]         # (..., 1, 1)
        return jnp.take_along_axis(tab, idx, axis=-2)[..., 0, :]

    def step(res, d):
        for _ in range(w):
            res = mm(res, res)
        return mm(res, select(d)), None

    res = select(wv[..., 0])
    if unroll:
        for j in range(1, nwin):
            res, _ = step(res, wv[..., j])
    elif nwin > 1:
        wv_t = jnp.moveaxis(wv[..., 1:], -1, 0)            # (nwin-1, ...)
        res, _ = jax.lax.scan(step, res, wv_t)
    return res


def _mod_exp_jnp(base: jax.Array, exp_bits: jax.Array, ctx: MontCtx,
                 lazy: bool = True, window: int | None = None,
                 unroll: bool = False) -> jax.Array:
    x = to_mont(jnp.asarray(base, U32), ctx, backend="jnp")
    if isinstance(ctx, MontLanes):
        # R mod n_i per lane, REDC(R^2 * 1), as the ladder kernel makes it
        one = from_mont(jnp.asarray(ctx.r2_digits, U32), ctx, backend="jnp")
    else:
        one = jnp.asarray(ctx.one_digits, U32)
    res = _windowed_ladder(
        lambda a, b: _mont_mul_jnp(a, b, ctx, lazy), one, x, exp_bits,
        window, unroll=unroll)
    return from_mont(res, ctx, backend="jnp")


def _bits_to_int(bits: np.ndarray) -> int:
    e = 0
    for v in bits:
        e = (e << 1) | int(v)
    return e


def _mod_exp_lanes_ref(b2: np.ndarray, eb: np.ndarray,
                       moduli: list) -> np.ndarray:
    """(N, m) bases, MSB-first exponent bits ((nbits,) or (N, nbits)) and
    one python-int modulus a lane -> (N, m) digits of b_i**e_i mod n_i."""
    from repro.kernels.dot_modmul import ref as _ref
    lanes = b2.shape[0]
    eb = np.asarray(eb, np.uint32)
    exps = ([_bits_to_int(eb)] * lanes if eb.ndim == 1 else
            [_bits_to_int(r) for r in eb.reshape(lanes, eb.shape[-1])])
    return _ref.mod_exp_ref_lanes(b2, exps, moduli)


def _mod_exp_reference(base, exp_bits, ctx) -> jax.Array:
    base = np.asarray(base, np.uint32)
    eb = np.asarray(exp_bits, np.uint32)
    b2, batch_shape = _flatten_batch(base, ctx.m)
    if eb.ndim > 1:
        eb = np.broadcast_to(eb, batch_shape + (eb.shape[-1],))
    out = _mod_exp_lanes_ref(b2, eb, _lane_moduli(ctx, b2.shape[0]))
    return jnp.asarray(out.reshape(batch_shape + (ctx.m,)))


def _mod_exp_reference_cb(b2: jax.Array, eb: jax.Array, ctx) -> jax.Array:
    """The host oracle as a jit-safe tier: the guarded dispatchers run at
    trace time, where b2/eb (and a per-lane context's rows) are tracers,
    so the python-int recompute is deferred to runtime via pure_callback,
    with each lane's modulus passed in beside its base."""
    n_rows = jnp.broadcast_to(jnp.asarray(ctx.n_digits, U32), b2.shape)

    def _host(base_np, eb_np, n_np):
        moduli = [L.limbs_to_int(r, DIGIT_BITS) for r in np.asarray(n_np)]
        return _mod_exp_lanes_ref(np.asarray(base_np), eb_np,
                                  moduli).astype(np.uint32)
    shape = jax.ShapeDtypeStruct(b2.shape[:-1] + (ctx.m,), np.uint32)
    return jax.pure_callback(_host, shape, b2, eb, n_rows)


def select_modexp_backend(nbits: int, batch: int = 1, ebits: int = 0,
                          ctx=None) -> str:
    """Batch-aware modexp dispatch (configs/dot_bignum.MODEXP_DISPATCH),
    the modexp twin of core/mul.select_method.

    The fused full-ladder kernels amortize over the batch axis only, so
    tiny batches (and tiny exponents, where the table build dominates)
    take the composition ladders -- but the floor is
    ``packed_min_batch``, not a full tile: the kernel wrappers pad
    sub-tile batches up to kernels/common/tiling.MIN_TILE and the
    padded lanes ride the sublane axis for free.  A BarrettCtx (even
    modulus) routes to the fused Barrett ladder in the same regime and
    to the jnp Barrett composition below it.  A
    ``repro.api.configure(modexp_backend=...)`` override wins over
    everything (ops knob for A/B experiments without code changes); the
    REPRO_MODEXP_BACKEND env var is its deprecated alias."""
    from repro import config as _rc
    from repro.configs.dot_bignum import MODEXP_DISPATCH as cfg
    from repro.obs import trace as _trace

    override = _rc.resolve("modexp_backend", BACKENDS, "modexp backend")
    fused_ok = (batch >= cfg.packed_min_batch
                and nbits <= cfg.fused_max_bits
                and ebits >= cfg.fused_min_exp_bits)
    detail = {"ebits": ebits, "fused_ok": fused_ok}
    if override:
        choice, rule = _resolve_backend(override, ctx), "override"
    elif isinstance(ctx, BarrettCtx):
        choice = "barrett_fused" if fused_ok else "barrett"
        rule = "barrett_ctx_fused" if fused_ok else "barrett_ctx"
    elif _DEFAULT_BACKEND != "jnp":
        # an explicit set_default_backend() choice wins over the
        # size-based dispatch (force "jnp" via backend= or the env var)
        choice, rule = _DEFAULT_BACKEND, "default_backend"
    elif fused_ok:
        choice, rule = "pallas", "fused_thresholds"
    else:
        choice, rule = "jnp", "below_fused_thresholds"
    _trace.emit("modexp", nbits, batch, choice, rule, **detail)
    return choice


def mod_exp(base: jax.Array, exp_bits: jax.Array, ctx,
            lazy: bool = True, backend: str | None = None,
            window: int | None = None) -> jax.Array:
    """base ** e mod n via the fixed-window constant-time ladder.

    base: (..., m) digits; exp_bits: (nbits,) or (..., nbits) uint32/int32
    bits MSB-first.  Every backend runs the same windowed schedule
    (see _windowed_ladder): ~nbits * (1 + 1/w) + 2**w modular multiplies
    instead of the bit-serial ladder's ~2 * nbits, exponent bits only
    ever feeding branch-free table gathers.  ``window`` overrides the
    config-picked window size w (configs/dot_bignum.pick_modexp_window).

    ``backend=None`` auto-selects via select_modexp_backend: the fused
    full-ladder Pallas kernel (ONE launch per modexp, power table
    VMEM-resident across all steps) for kernel-sized batches, the jnp
    windowed composition below that; a BarrettCtx (even modulus)
    auto-routes to the Barrett ladder.  ``lazy`` applies to the jnp
    backend only (see mont_mul)."""
    eb = jnp.asarray(exp_bits, U32)
    if backend is None:
        batch = 1
        for d in jnp.broadcast_shapes(jnp.shape(base)[:-1], eb.shape[:-1]):
            batch *= int(d)
        backend = select_modexp_backend(
            ctx.m * DIGIT_BITS, batch, ebits=eb.shape[-1], ctx=ctx)
    else:
        backend = _resolve_backend(backend, ctx)
    if backend == "barrett":
        return _barrett_mod_exp(base, exp_bits, ctx, window)
    if backend == "jnp":
        return _mod_exp_jnp(base, exp_bits, ctx, lazy, window)
    if backend in ("pallas", "barrett_fused"):
        from repro.kernels.dot_modmul import ops as _mops
        from repro.resilience import guard as _guard
        kctx = _as_barrett(ctx) if backend == "barrett_fused" else ctx
        base = jnp.asarray(base, U32)
        # broadcast BOTH operands to the joint batch shape before
        # flattening (shared base x per-lane exponents and vice versa)
        shape = jnp.broadcast_shapes(
            base.shape[:-1], eb.shape[:-1]) + (kctx.m,)
        b2, batch_shape = _flatten_batch(
            jnp.broadcast_to(base, shape), kctx.m)
        if eb.ndim > 1:
            eb = jnp.broadcast_to(
                eb, batch_shape + (eb.shape[-1],)).reshape(-1, eb.shape[-1])
        eb2 = eb
        if backend == "barrett_fused":
            tiers = [
                ("barrett_fused", lambda: _mops.dot_barrett_mod_exp(
                    b2, eb2, kctx, window=window)),
                ("barrett", lambda: _barrett_mod_exp(b2, eb2, kctx, window)),
                ("reference", lambda: _mod_exp_reference_cb(b2, eb2, kctx)),
            ]
        else:
            tiers = [
                ("pallas", lambda: _mops.dot_mod_exp(
                    b2, eb2, kctx, window=window)),
                ("jnp", lambda: _mod_exp_jnp(b2, eb2, kctx, lazy, window)),
                ("reference", lambda: _mod_exp_reference_cb(b2, eb2, kctx)),
            ]
        out = _guard.run("modexp", kctx.m * DIGIT_BITS, tiers)
        return out.reshape(batch_shape + (kctx.m,))
    return _mod_exp_reference(base, exp_bits, ctx)


def exp_bits_msb(e: int, nbits: int | None = None) -> np.ndarray:
    """MSB-first bit array of e, padded (never truncated) to nbits."""
    if e < 0:
        raise ValueError(f"exp_bits_msb: exponent must be >= 0, got {e}")
    nbits = nbits or max(1, e.bit_length())
    if e.bit_length() > nbits:
        raise ValueError(
            f"exp_bits_msb: e needs {e.bit_length()} bits but nbits={nbits} "
            f"-- refusing to silently truncate the exponent")
    return np.array([(e >> (nbits - 1 - i)) & 1 for i in range(nbits)],
                    np.uint32)
