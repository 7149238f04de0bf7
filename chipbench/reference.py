"""The benchmark's own plain reference: python ints, nothing of the program.

Inputs are made here from the seed (RSA keys, request values), and the
answers the program served are judged here.  Nothing in this module
imports ``repro``: a later change to the program cannot move what
``correct`` means.
"""
from __future__ import annotations

import random

import numpy as np

_SMALL_PRIMES = [p for p in range(3, 2000)
                 if all(p % q for q in range(2, int(p ** 0.5) + 1))]


def to_ints(limbs) -> list:
    """(N, m) uint32 little-endian limbs -> python ints."""
    a = np.ascontiguousarray(np.asarray(limbs, np.uint32).astype("<u4"))
    return [int.from_bytes(row.tobytes(), "little") for row in a]


def to_limbs(values, nlimbs: int) -> np.ndarray:
    """python ints -> (N, nlimbs) uint32 little-endian limbs."""
    buf = b"".join(v.to_bytes(4 * nlimbs, "little") for v in values)
    return np.frombuffer(buf, "<u4").astype(np.uint32).reshape(-1, nlimbs)


def _probable_prime(n: int, rng: random.Random, rounds: int = 24) -> bool:
    for p in _SMALL_PRIMES:
        if n % p == 0:
            return n == p
    d, r = n - 1, 0
    while d % 2 == 0:
        d //= 2
        r += 1
    for _ in range(rounds):
        x = pow(rng.randrange(2, n - 2), d, n)
        if x in (1, n - 1):
            continue
        for _ in range(r - 1):
            x = x * x % n
            if x == n - 1:
                break
        else:
            return False
    return True


def _prime(bits: int, rng: random.Random) -> int:
    while True:
        # top two bits set, so the product of two such primes has
        # exactly 2 * bits bits
        cand = rng.getrandbits(bits) | (3 << (bits - 2)) | 1
        if _probable_prime(cand, rng):
            return cand


def rsa_key(bits: int, e: int, seed: int) -> dict:
    """An RSA key {n, e, d, p, q, bits} made from ``seed`` alone."""
    rng = random.Random(f"rsa-key/{bits}/{e}/{seed}")
    while True:
        p, q = _prime(bits // 2, rng), _prime(bits - bits // 2, rng)
        phi = (p - 1) * (q - 1)
        if p == q or phi % e == 0:
            continue
        try:
            d = pow(e, -1, phi)
        except ValueError:
            continue
        return {"n": p * q, "e": e, "d": d, "p": p, "q": q, "bits": bits}


def rsa_private(m: int, key: dict) -> int:
    """m ** d mod n by the Chinese remainder theorem (the same value as
    ``pow(m, d, n)``, a quarter of the work)."""
    p, q, d = key["p"], key["q"], key["d"]
    mp, mq = pow(m, d % (p - 1), p), pow(m, d % (q - 1), q)
    h = pow(q, -1, p) * (mp - mq) % p
    return mq + h * q


def rsa_wrong(op: str, values: list, results: list, key: dict,
              sample: list) -> int:
    """How many served answers differ from the reference.

    ``rsa_verify``: every answer against ``pow(v, e, n)``.
    ``rsa_sign``: every answer by the public exponent (``s < n`` and
    ``s ** e == m mod n``, which holds for ``s = m ** d mod n`` and no
    other residue, since ``x -> x ** e`` permutes the residues mod n),
    and the answers at the indices in ``sample`` against ``m ** d mod
    n`` itself."""
    n, e = key["n"], key["e"]
    wrong = set()
    for i, (v, s) in enumerate(zip(values, results)):
        if op == "rsa_verify":
            ok = s == pow(v, e, n)
        else:
            ok = s < n and pow(s, e, n) == v
        if not ok:
            wrong.add(i)
    if op == "rsa_sign":
        wrong.update(i for i in sample if results[i] != rsa_private(
            values[i], key))
    return len(wrong)


def mul_wrong(a, b, out) -> int:
    """Rows of ``out`` (N, 2m) that are not the product of the rows of
    ``a`` and ``b`` (N, m)."""
    return sum(x * y != z for x, y, z in zip(to_ints(a), to_ints(b),
                                             to_ints(out)))
