"""Python-int oracle for the fused Montgomery kernel.

Python ints ARE the reference bignum implementation (see core/limbs.py):
the oracle computes a*b*R^{-1} mod n and x^e mod n exactly, host-side,
digit-for-digit comparable with the kernel output.  Unlike dot_add/ref
(which reuses the jnp core path), the Montgomery oracle is deliberately
independent of ALL jnp code so a kernel bug and a core/modular.py bug
cannot cancel.
"""
from __future__ import annotations

import numpy as np

from repro.core import limbs as L

DIGIT_BITS = 16


def mont_mul_int_ref(a: int, b: int, n: int, m: int) -> int:
    """a * b * R^{-1} mod n with R = 2**(16*m), via pow()."""
    R = 1 << (DIGIT_BITS * m)
    return (a * b * pow(R, -1, n)) % n


def _per_lane(n, lanes: int) -> list:
    """One modulus for every lane (an int), or a sequence of one a lane."""
    ns = [n] * lanes if isinstance(n, int) else [int(v) for v in n]
    assert len(ns) == lanes, (len(ns), lanes)
    return ns


def mont_mul_ref(a_digits: np.ndarray, b_digits: np.ndarray,
                 n) -> np.ndarray:
    """(batch, m) digit arrays -> (batch, m) digits of a*b*R^{-1} mod n;
    ``n`` is one modulus or a sequence of one per lane."""
    a_digits = np.asarray(a_digits)
    b_digits = np.asarray(b_digits)
    m = a_digits.shape[-1]
    outs = []
    for i, ni in enumerate(_per_lane(n, a_digits.shape[0])):
        x = L.limbs_to_int(a_digits[i], DIGIT_BITS)
        y = L.limbs_to_int(b_digits[i], DIGIT_BITS)
        outs.append(L.int_to_limbs(mont_mul_int_ref(x, y, ni, m),
                                   m, DIGIT_BITS))
    return np.stack(outs)


def mod_exp_ref(base_digits: np.ndarray, e: int, n: int) -> np.ndarray:
    """(batch, m) digits -> (batch, m) digits of base**e mod n."""
    base_digits = np.asarray(base_digits)
    m = base_digits.shape[-1]
    outs = []
    for i in range(base_digits.shape[0]):
        x = L.limbs_to_int(base_digits[i], DIGIT_BITS)
        outs.append(L.int_to_limbs(pow(x, e, n), m, DIGIT_BITS))
    return np.stack(outs)


def mod_exp_ref_lanes(base_digits: np.ndarray, exps: list[int],
                      n) -> np.ndarray:
    """Per-lane oracle for the batched-exponent ladder: lane i computes
    base[i] ** exps[i] mod n_i (host pow, exact), where ``n`` is one
    modulus or a sequence of one per lane."""
    base_digits = np.asarray(base_digits)
    m = base_digits.shape[-1]
    assert base_digits.shape[0] == len(exps)
    outs = []
    for i, (e, ni) in enumerate(zip(exps, _per_lane(n, len(exps)))):
        x = L.limbs_to_int(base_digits[i], DIGIT_BITS)
        outs.append(L.int_to_limbs(pow(x, int(e), ni), m, DIGIT_BITS))
    return np.stack(outs)
