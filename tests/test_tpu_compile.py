"""Compile the main-path Pallas kernels for a described TPU v5e.

Nothing runs: each case lowers a kernel wrapper at a deployment width
with ``interpret=False`` and compiles it with the TPU compiler for one
device of a described ``v5e:2x2`` topology.  That catches what
interpret mode cannot -- ops Mosaic does not lower (scatter, dynamic
lane slices, shape casts) and tiles over the scoped VMEM limit -- and
checks the program really holds a kernel (``tpu_custom_call``) rather
than a fallback.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.dot_bignum import pick_modexp_window
from repro.kernels.common import tiling
from repro.kernels.dot_div import ops as div_ops
from repro.kernels.dot_modmul import kernel as modmul_kernel
from repro.kernels.dot_modmul import ops as modmul_ops
from repro.kernels.dot_mul import ops as mul_ops
from repro.kernels.kara_mul import ops as kara_ops
from repro.kernels.ntt_mul import ops as ntt_ops

U32 = jnp.uint32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, *dims):
    return jax.ShapeDtypeStruct(dims, U32, sharding=sharding)


def _ladder(s, bits, batch=8):
    m = bits // 16
    w = pick_modexp_window(bits)
    nwin = -(-bits // w)
    tb = tiling.batch_tile(m, batch, budget=tiling.budget_words(
        modmul_kernel.ladder_live_arrays(w)), max_tile=modmul_kernel.MAX_TILE)
    row = _shape(s, 1, m)
    return modmul_ops._ladder_call.lower(
        _shape(s, batch, m), _shape(s, batch, nwin), row, row, row,
        tb=tb, n0p=0x1235, window=w, interpret=False)


def _barrett_ladder(s, bits, batch=8):
    m = bits // 16
    w = pick_modexp_window(bits)
    nwin = -(-bits // w)
    tb = tiling.batch_tile(m, batch, budget=tiling.budget_words(
        modmul_kernel.barrett_live_arrays(w)),
        max_tile=modmul_kernel.MAX_TILE)
    return modmul_ops._barrett_ladder_call.lower(
        _shape(s, batch, m), _shape(s, batch, nwin), _shape(s, 1, m),
        _shape(s, 1, m + 2), tb=tb, window=w, interpret=False)


def _kara(s, bits, batch=4096):
    m = bits // 16
    return kara_ops._call.lower(
        _shape(s, batch, m), _shape(s, batch, m),
        tb=kara_ops._heuristic_tile(m, batch),
        threshold=kara_ops.K.DEFAULT_THRESHOLD, base_mode="rows",
        interpret=False)


def _dot_mul(s, bits, batch=256):
    m = bits // 16
    return mul_ops._call.lower(_shape(s, batch, m), _shape(s, batch, m),
                               tb=mul_ops._heuristic_tile(m, batch),
                               interpret=False)


def _dot_div(s, bits, batch=256):
    na, nb = bits // 16, bits // 32             # a bits-wide over b half
    return div_ops._call.lower(_shape(s, batch, na + nb),
                               _shape(s, batch, nb),
                               tb=div_ops._heuristic_tile(na + nb, batch),
                               interpret=False)


def _ntt(s, bits, batch=64):
    nd = bits // 16
    n = ntt_ops.next_pow2(2 * nd)
    nprimes = ntt_ops._resolve_nprimes(nd, None)
    stages = n.bit_length() - 1
    tw = tuple((_shape(s, stages, n), _shape(s, stages, n))
               for _ in range(nprimes))
    return ntt_ops._call.lower(_shape(s, batch, nd), _shape(s, batch, nd),
                               tw, nprimes=nprimes,
                               tb=ntt_ops._heuristic_tile(n, batch),
                               interpret=False)


CASES = {
    "montgomery_ladder-1024": (_ladder, 1024),   # RSA-2048 CRT halves
    "montgomery_ladder-2048": (_ladder, 2048),
    "barrett_ladder-1024": (_barrett_ladder, 1024),
    "kara_mul-1024": (_kara, 1024),
    "kara_mul-2048": (_kara, 2048),
    "kara_mul-4096": (_kara, 4096),
    "dot_mul-512": (_dot_mul, 512),
    "dot_div-512": (_dot_div, 512),
    "ntt_mul-16384": (_ntt, 16384),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    build, bits = CASES[case]
    compiled = build(one_chip, bits).compile()
    assert "tpu_custom_call" in compiled.as_text()
