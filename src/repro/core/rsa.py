"""RSA-style batched sign/verify on top of DoT modular arithmetic.

The OpenSSL-speed analogue (paper Fig. 5): throughput/latency of modexp-
bound crypto, batched across TPU lanes.  Key generation runs host-side
with Python integers (Miller-Rabin) -- the launcher's job, like loading
certificates; all per-message math runs in JAX via core.modular.

sign and verify take one key for the whole batch (``RSAKey``) or one key a
lane (``KeyLanes``: keys of one width, lane i signing with d_i modulo
n_i), so a server with many certificates fills one batch and one
compiled program with all of them.

This module also provides the checkpoint-integrity signer used by
train/checkpoint.py (dogfooding: the framework's own fault-tolerance
layer rides on the paper's arithmetic).
"""
from __future__ import annotations

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import limbs as L
from repro.core import modular as M

U32 = jnp.uint32
DIGIT_BITS = 16


# ---------------------------------------------------------------------------
# host-side keygen (python ints)
# ---------------------------------------------------------------------------

_SMALL_PRIMES = [2, 3, 5, 7, 11, 13, 17, 19, 23, 29, 31, 37, 41, 43, 47]


def _is_probable_prime(n: int, rng: np.random.Generator, rounds: int = 12) -> bool:
    if n < 2:
        return False
    for p in _SMALL_PRIMES:
        if n % p == 0:
            return n == p
    d, r = n - 1, 0
    while d % 2 == 0:
        d //= 2
        r += 1
    for _ in range(rounds):
        a = int(rng.integers(2, min(n - 2, 1 << 62)))
        x = pow(a, d, n)
        if x in (1, n - 1):
            continue
        for _ in range(r - 1):
            x = x * x % n
            if x == n - 1:
                break
        else:
            return False
    return True


def _gen_prime(bits: int, rng: np.random.Generator) -> int:
    while True:
        raw = L.random_bigints(rng, 1, bits)[0]
        cand = raw | (1 << (bits - 1)) | 1
        if _is_probable_prime(cand, rng):
            return cand


@dataclasses.dataclass(frozen=True)
class RSAKey:
    n: int
    e: int
    d: int
    bits: int
    p: int = 0                   # prime factors (0: unknown -- no CRT)
    q: int = 0

    @property
    def ctx(self) -> M.MontCtx:
        return M.mont_setup(self.n, self.bits)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class KeyLanes:
    """RSA keys of one width, one a lane: lane i signs with d_i and
    verifies with e_i modulo n_i.  A pytree of arrays (``bits`` is
    static), so a key table can be a jitted program's argument and
    ``take`` gathers each lane's rows inside the program."""
    n_digits: jax.Array          # (N, m) modulus digits
    r2_digits: jax.Array         # (N, m) R^2 mod n
    n0p: jax.Array               # (N,) -n^{-1} mod B
    d_bits: jax.Array            # (N, bits) d, MSB first, padded to bits
    e_bits: jax.Array            # (N, ebits) e, MSB first
    bits: int = dataclasses.field(metadata=dict(static=True))

    @property
    def ctx(self) -> M.MontLanes:
        return M.MontLanes(m=self.n_digits.shape[-1], n_digits=self.n_digits,
                           r2_digits=self.r2_digits, n0p=self.n0p)

    def take(self, idx) -> "KeyLanes":
        """The keys at rows ``idx`` ((N,) ints), one a lane."""
        return jax.tree_util.tree_map(lambda x: x[idx], self)


def key_lanes(keys) -> KeyLanes:
    """One row a key, on the host, for keys of one width."""
    bits = keys[0].bits
    if any(k.bits != bits for k in keys):
        raise ValueError(f"key_lanes: keys of one width only, got "
                         f"{sorted({k.bits for k in keys})} bits")
    ctx = M.mont_lanes([k.n for k in keys], bits)
    ebits = max(k.e.bit_length() for k in keys)
    return KeyLanes(
        n_digits=ctx.n_digits, r2_digits=ctx.r2_digits, n0p=ctx.n0p,
        d_bits=np.stack([M.exp_bits_msb(k.d, bits) for k in keys]),
        e_bits=np.stack([M.exp_bits_msb(k.e, ebits) for k in keys]),
        bits=bits)


def generate_key(bits: int = 512, seed: int = 0, e: int = 65537) -> RSAKey:
    rng = np.random.default_rng(seed)
    while True:
        p = _gen_prime(bits // 2, rng)
        q = _gen_prime(bits - bits // 2, rng)
        if p == q:
            continue
        n = p * q
        phi = (p - 1) * (q - 1)
        if np.gcd(e, 1) and phi % e != 0:
            try:
                d = pow(e, -1, phi)
            except ValueError:
                continue
            return RSAKey(n=n, e=e, d=d, bits=bits, p=p, q=q)


# ---------------------------------------------------------------------------
# batched sign / verify (JAX)
# ---------------------------------------------------------------------------

def messages_to_digits(msgs: list[int], key: RSAKey) -> jnp.ndarray:
    m_digits = key.ctx.m
    return jnp.asarray(np.stack(
        [L.int_to_limbs(msg % key.n, m_digits, DIGIT_BITS) for msg in msgs]))


def sign(msg_digits: jax.Array, key: RSAKey | KeyLanes,
         backend: str | None = None) -> jax.Array:
    """s = m^d mod n, batched over leading axes (with ``KeyLanes``, one
    (N, m) batch whose lane i is signed under key i).

    ``backend=None`` routes through core/modular's batch-aware modexp
    dispatch (MODEXP_DISPATCH): the fused full-ladder Pallas kernel for
    kernel-sized batches, the jnp windowed ladder below that."""
    if isinstance(key, KeyLanes):
        bits = key.d_bits
    else:
        bits = jnp.asarray(M.exp_bits_msb(key.d, key.n.bit_length()))
    return M.mod_exp(msg_digits, bits, key.ctx, backend=backend)


def verify(sig_digits: jax.Array, key: RSAKey | KeyLanes,
           backend: str | None = None) -> jax.Array:
    """m = s^e mod n (fast public exponent; the windowed ladder picks a
    small window for the 17-bit e, see pick_modexp_window)."""
    if isinstance(key, KeyLanes):
        bits = key.e_bits
    else:
        bits = jnp.asarray(M.exp_bits_msb(key.e))
    return M.mod_exp(sig_digits, bits, key.ctx, backend=backend)


def decrypt_crt(c_digits: jax.Array, key: RSAKey,
                backend: str | None = None) -> jax.Array:
    """m = c^d mod n via the Chinese Remainder Theorem: two HALF-SIZE
    modexps (c^{d mod p-1} mod p, c^{d mod q-1} mod q) recombined with
    Garner's formula -- ~4x fewer digit-multiply work than the full
    ladder, the standard RSA private-key optimization.  Both half-size
    modexps ride the windowed ladder via the same backend dispatch as
    sign/verify (``backend=None`` -> MODEXP_DISPATCH auto-select).

    The recombination runs on device on the division subsystem: p and q
    are HOST-known key constants, so every mod-p/mod-q reduction is a
    core/div.divmod_const (exact host reciprocal: one pipeline multiply
    + a branch-free fix -- no Newton chain in the hot path) and the
    cross-products ride the multiply pipeline.  Host-side: only the
    per-key constants (d mod p-1, d mod q-1, q^{-1} mod p).
    """
    from repro.core import div as DV

    if not (key.p and key.q):
        raise ValueError("decrypt_crt needs a key with known p, q factors")
    p, q = key.p, key.q
    ctx_p = M.mont_setup(p)
    ctx_q = M.mont_setup(q)
    mp, mq, mn = ctx_p.m, ctx_q.m, key.ctx.m
    dp_bits = jnp.asarray(M.exp_bits_msb(key.d % (p - 1), p.bit_length()))
    dq_bits = jnp.asarray(M.exp_bits_msb(key.d % (q - 1), q.bit_length()))
    p_dig = jnp.asarray(L.int_to_limbs(p, mp, DIGIT_BITS))
    q_dig = jnp.asarray(L.int_to_limbs(q, mq, DIGIT_BITS))
    qinv = pow(q, -1, p)
    qinv_dig = jnp.asarray(L.int_to_limbs(qinv, mp, DIGIT_BITS))

    c = jnp.asarray(c_digits, U32)
    c_p = DV.divmod_const(c, p)[1][..., :mp]                # c mod p
    c_q = DV.divmod_const(c, q)[1][..., :mq]
    m1 = M.mod_exp(c_p, dp_bits, ctx_p, backend=backend)    # (.., mp)
    m2 = M.mod_exp(c_q, dq_bits, ctx_q, backend=backend)    # (.., mq)

    # Garner: h = qinv * (m1 - m2) mod p;  m = m2 + h*q
    m2_p = DV.divmod_const(m2, p)[1][..., :mp]              # m2 mod p
    w = mp + 1
    t = DV.add_digits(DV._pad_to(m1, w), DV._pad_to(p_dig, w))
    t, _ = DV.sub_digits(t, DV._pad_to(m2_p, w))            # < 2p
    over = DV.ge_digits(t, DV._pad_to(p_dig, w))
    t = DV.sub_digits(t, DV._pad_to(p_dig, w) * over[..., None])[0]
    # q^-1 and q are host key constants: at huge key sizes these Garner
    # cross-products ride the prepared-operand NTT cache like the
    # divmod_const reductions above them
    prod = DV._mul_equalized(t[..., :mp], qinv_dig,
                             b_const=qinv)                  # (.., 2mp)
    h = DV.divmod_const(prod, p)[1][..., :mp]               # (.., mp)
    hq = DV._mul_equalized(h, q_dig, b_const=q)[..., :mn]   # h*q < n
    return DV.add_digits(DV._pad_to(m2, mn), hq)


def digest_int(data: bytes, bits: int) -> int:
    h = b""
    i = 0
    while len(h) * 8 < bits:
        h += hashlib.sha256(data + i.to_bytes(4, "big")).digest()
        i += 1
    return int.from_bytes(h, "big") % (1 << (bits - 1))
