"""Production mesh construction.

Importing this module never touches JAX device state; meshes are built
lazily inside functions (so smoke tests see 1 device while the dry-run,
which sets XLA_FLAGS before any import, sees 512).

Production target: TPU v5e pods, 256 chips each (16x16 mesh per pod);
the multi-pod configuration adds a leading "pod" axis over DCN.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """jax.make_mesh with Auto axes: the model code places activations
    with with_sharding_constraint, which asserts instead of resharding
    on the Explicit axes jax.make_mesh gives by default."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (possibly fake) local devices exist;
    used by subprocess-based distribution tests."""
    return auto_mesh((data, model), ("data", "model"))


# Hardware constants for the roofline (TPU v5e per chip)
PEAK_FLOPS_BF16 = 197e12     # FLOP/s
HBM_BW = 819e9               # bytes/s
ICI_BW_PER_LINK = 50e9       # bytes/s/link (~6 links usable per chip on a
                             # 2D torus slice; roofline uses chips x link_bw
                             # per the assignment's formula)
