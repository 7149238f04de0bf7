"""Oracles for the fused NTT multiply kernel.

``ntt_mul_digits_ref`` is the jnp Karatsuba composition (itself
oracle-tested against Python ints in tests/test_mul.py); tests/
test_ntt_mul.py additionally checks digits against Python-int ground
truth directly so a kernel bug and a core/mul.py bug cannot cancel.
``ntt_fwd_ref`` is an O(N**2) Python-int DFT used to pin down the
transform itself (twiddle tables, stage order, bit-reversed layout)
independently of the inverse that would undo a systematic error.
``crt_combine_ref`` is the plain-jnp Garner recombination (scatter-add
columns, one carry resolve) the ``crt_combine`` kernel replaced; the
tests hold the kernel to it bit for bit.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.mul import mul_karatsuba, mul_limbs32
from repro.kernels.common.carry import normalize_static
from repro.kernels.ntt_mul import kernel as K
from repro.kernels.ntt_mul.kernel import GENERATOR


def ntt_mul_digits_ref(a_digits, b_digits):
    return mul_karatsuba(a_digits, b_digits)


def ntt_mul_limbs32_ref(a_limbs, b_limbs):
    return mul_limbs32(a_limbs, b_limbs, method="karatsuba")


def _bit_reverse(i: int, bits: int) -> int:
    out = 0
    for _ in range(bits):
        out = (out << 1) | (i & 1)
        i >>= 1
    return out


def ntt_fwd_ref(x, p: int) -> np.ndarray:
    """Length-N forward NTT mod p by direct evaluation (Python ints),
    returned in the BIT-REVERSED order the DIF kernel produces."""
    n = len(x)
    w = pow(GENERATOR, (p - 1) // n, p)
    nat = [sum(int(x[j]) * pow(w, i * j, p) for j in range(n)) % p
           for i in range(n)]
    bits = n.bit_length() - 1
    return np.array([nat[_bit_reverse(i, bits)] for i in range(n)],
                    np.uint32)


def crt_combine_ref(residues, out_digits: int):
    """Per-prime residues (..., >= out_digits) -> (..., out_digits)
    normalized digits, in plain jnp: v = r1 + p1*t2 (+ p1*p2*t3) as
    16-bit half products scatter-added into lazy columns, then one
    ``normalize_static`` resolve."""
    nprimes = len(residues)
    c = K.garner_constants(nprimes)
    p2 = K.PRIMES[1]
    u32 = np.uint32
    r1 = residues[0][..., :out_digits]
    t2 = K.mont_mul(K.sub_mod(residues[1][..., :out_digits], r1, p2),
                    u32(c["inv1_mont2"]), p2, c["pinv2"])
    cols = jnp.zeros(r1.shape[:-1] + (out_digits + 8,), jnp.uint32)

    def acc(cols, vals, off):
        return cols.at[..., off:off + out_digits].add(vals)

    def acc_prod(cols, t, const_digits):
        for k, ck in enumerate(const_digits):
            for part, o in ((t & K.DMASK, 0), (t >> u32(16), 1)):
                prod = part * u32(ck)
                cols = acc(cols, prod & K.DMASK, k + o)
                cols = acc(cols, prod >> u32(16), k + o + 1)
        return cols

    cols = acc(cols, r1 & K.DMASK, 0)
    cols = acc(cols, r1 >> u32(16), 1)
    cols = acc_prod(cols, t2, c["p1_digits"])
    if nprimes >= 3:
        p3 = K.PRIMES[2]
        c12 = K.add_mod(r1, K.mont_mul(t2, u32(c["p1_mont3"]), p3,
                                       c["pinv3"]), p3)
        t3 = K.mont_mul(K.sub_mod(residues[2][..., :out_digits], c12, p3),
                        u32(c["inv12_mont3"]), p3, c["pinv3"])
        cols = acc_prod(cols, t3, c["q_digits"])
    norm = normalize_static(cols, K.DIGIT_BITS,
                            bound=K.CRT_COLUMN_TERMS << K.DIGIT_BITS)
    return norm[..., :out_digits]
