"""Shared VnC (vertical-and-crosswise) building blocks for multiply kernels.

Two realizations of the same Phase 1-4 math (all partial products,
aligned to columns, reduced with deferred carries):

* ``vnc_cols_rows``: an unrolled row loop of slice-adds -- the VPU-native
  schedule (each step is one full-width multiply plus two lane-aligned
  accumulations; no m-fold memory blowup).  Best on TPU.
* ``vnc_cols_skew``: materialize the full (..., m, m) product triangle
  and reduce it via the static skew-reshape -- one big vectorized
  contraction instead of m dependent updates.  Best where the serial
  row-loop chain dominates (CPU interpret mode); memory is O(m) larger.

Kernel wrappers pick per backend (see kara_mul/ops.py); both are exact
for digits < 2**16 held in uint32.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels.common.carry import add_at

U32 = jnp.uint32
DBITS = 16
DMASK = np.uint32((1 << DBITS) - 1)


def skew(mat):
    """out[..., i, i+j] = mat[..., i, j]: anti-diagonals become columns."""
    *lead, m, m2 = mat.shape
    assert m == m2, "square (..., m, m) expected"
    pad = jnp.pad(mat, [(0, 0)] * len(lead) + [(0, 0), (0, m)])
    flat = pad.reshape(*lead, m * 2 * m)
    flat = flat[..., : m * (2 * m - 1)]
    return flat.reshape(*lead, m, 2 * m - 1)


def vnc_cols_rows(a, b):
    """(..., nb) x2 uint32 digits -> (..., 2nb) lazy cols (row-loop form).

    Works for any leading batch shape; the loop is unrolled at trace
    time (nb static).  The lo and hi halves of each row are pre-combined
    into one (nb+1)-wide lane vector so each step costs a single
    accumulate into the column buffer (halving the update traffic of the
    naive two-slice-add schedule).
    """
    nb = a.shape[-1]
    cols = jnp.zeros(a.shape[:-1] + (2 * nb,), U32)
    z1 = jnp.zeros(a.shape[:-1] + (1,), U32)
    for i in range(nb):
        prod = a[..., i:i + 1] * b               # exact uint32 products
        row = (jnp.concatenate([prod & DMASK, z1], axis=-1)
               + jnp.concatenate([z1, prod >> np.uint32(DBITS)], axis=-1))
        cols = add_at(cols, i, row)              # lo at c, hi at c+1
    return cols


def vnc_cols_skew(a, b):
    """(..., nb) x2 uint32 digits -> (..., 2nb) lazy cols (skew form)."""
    nb = a.shape[-1]
    prod = a[..., :, None] * b[..., None, :]     # (..., nb, nb) exact
    lo = skew(prod & DMASK).sum(axis=-2)         # (..., 2nb-1)
    hi = skew(prod >> np.uint32(DBITS)).sum(axis=-2)
    zeros1 = jnp.zeros(a.shape[:-1] + (1,), U32)
    cols = jnp.concatenate([lo, zeros1], axis=-1)
    return cols + jnp.concatenate([zeros1, hi], axis=-1)   # hi -> c+1
