"""Compile the main-path Pallas kernels for a described TPU v5e.

Nothing runs: each case lowers a kernel wrapper at a deployment width
with ``interpret=False`` and compiles it with the TPU compiler for one
device of a described ``v5e:2x2`` topology.  That catches what
interpret mode cannot -- ops Mosaic does not lower (scatter, dynamic
lane slices, shape casts) and tiles over the scoped VMEM limit -- and
checks the program really holds a kernel (``tpu_custom_call``) rather
than a fallback.  The lowered module names each kernel (its custom
call's ``kernel_name``); the compiled program keeps the instruction
names the chip benchmark's kernel metrics key on.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import api
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
    # base, window values, and each lane's modulus, R^2 mod n and n0p
    return modmul_ops._ladder_call.lower(
        _shape(s, batch, m), _shape(s, batch, nwin), _shape(s, batch, m),
        _shape(s, batch, m), _shape(s, batch, 1),
        tb=tb, window=w, interpret=False)


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


def _crt(s, bits, batch=4096):
    nd = bits // 16
    n = ntt_ops.next_pow2(2 * nd)
    nprimes = ntt_ops._resolve_nprimes(nd, None)
    res = [_shape(s, batch, n) for _ in range(nprimes)]
    return jax.jit(lambda *r: ntt_ops.crt_combine(
        r, 2 * nd, interpret=False)).lower(*res)


CASES = {   # case: (build, bits, the kernel_names of its kernels)
    # the CRT halves of an RSA-2048 key
    "montgomery_ladder-1024": (_ladder, 1024, {"ladder_kernel"}),
    "montgomery_ladder-2048": (_ladder, 2048, {"ladder_kernel"}),
    "barrett_ladder-1024": (_barrett_ladder, 1024,
                            {"dot_modmul_barrett_ladder"}),
    "kara_mul-1024": (_kara, 1024, {"kara_kernel"}),
    "kara_mul-2048": (_kara, 2048, {"kara_kernel"}),
    "kara_mul-4096": (_kara, 4096, {"kara_kernel"}),
    "dot_mul-512": (_dot_mul, 512, {"dot_mul"}),
    "dot_div-512": (_dot_div, 512, {"dot_div"}),
    # one launch per prime, then the CRT recombination
    "ntt_mul-16384": (_ntt, 16384, {"ntt_mul_kernel", "crt_combine"}),
    "crt_combine-16384": (_crt, 16384, {"crt_combine"}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    build, bits, _ = CASES[case]
    compiled = build(one_chip, bits).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("case", sorted(CASES))
def test_lowered_kernel_carries_its_name(one_chip, case):
    build, bits, want = CASES[case]
    text = build(one_chip, bits).as_text()
    names = re.findall(r'kernel_name = "([^"]*)"', text)
    assert set(names) == want


def _rsa_sign_512(s):
    key = api.generate_key(512, seed=1)
    return jax.jit(lambda b: api.rsa_sign(b, key)).lower(_shape(s, 8, 16))


def _rsa_sign_512_keys(s):
    # the serving engine's program: a table of keys as an argument, each
    # lane's key gathered by its row
    table = api.key_lanes([api.generate_key(512, seed=k) for k in (1, 2, 3)])
    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), table)
    rows = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=s)
    return jax.jit(lambda b, r, t: api.rsa_sign(b, t.take(r))).lower(
        _shape(s, 8, 16), rows, shapes)


KEYED = {   # program: (build, the kernels' <instruction>/<operand count>)
    "mul-1024": (lambda s: jax.jit(api.mul).lower(_shape(s, 8, 32),
                                                  _shape(s, 8, 32)),
                 ["_call/2"]),                   # kara_mul
    "mul-8192": (lambda s: jax.jit(api.mul).lower(_shape(s, 8, 256),
                                                  _shape(s, 8, 256)),
                 # ntt_mul, one per prime, then the CRT recombination
                 ["_call/4", "_call/4", "crt_combine/2"]),
    "rsa_sign-512": (_rsa_sign_512, ["_ladder_call/5"]),
    "rsa_sign-512-keys": (_rsa_sign_512_keys, ["_ladder_call/5"]),
}


@pytest.mark.parametrize("program", sorted(KEYED))
def test_kernel_instruction_names_stay(one_chip, monkeypatch, program):
    # the chip benchmark's kernel metrics find a kernel on the device
    # trace by its HLO instruction, <name>/<operand count>; a pallas_call
    # name= or a named scope around a launch would rename it
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    build, want = KEYED[program]
    got = []
    for line in build(one_chip).compile().as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            name = line.split(" = ", 1)[0].split()[-1].lstrip("%")
            args = line.split(" custom-call(", 1)[1].split(")", 1)[0]
            got.append(f"{name.rsplit('.', 1)[0]}/{args.count('%')}")
    assert got == want


@pytest.fixture(scope="module")
def mul_8192_op_names(one_chip):
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        text = KEYED["mul-8192"][0](one_chip).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("scope", ["radix_split", "radix_join"])
def test_compiled_mul_carries_named_scopes(mul_8192_op_names, scope):
    # the jnp radix conversions around the ntt kernels keep their scopes
    # in the compiled program's op_name metadata, where a reader of the
    # compiled HLO attributes the device ops to them
    assert any(f"/{scope}/" in n for n in mul_8192_op_names)
