"""Multi-device behaviour on a subprocess mesh (8 fake host devices):
exact integer psum, int8 error-feedback psum, ring collective matmul,
pipeline parallelism, and elastic checkpoint restore across mesh shapes.

Each test runs a child interpreter because the parent's jax is locked to
1 device.
"""
import pathlib
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow  # each test compiles in a child interpreter

SRC = str(pathlib.Path(__file__).resolve().parent.parent / "src")


def run_child(code: str, devices: int = 8) -> str:
    env = {"XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
           "PYTHONPATH": SRC, "PATH": "/usr/bin:/bin"}
    import os
    env.update({k: v for k, v in os.environ.items()
                if k not in env and k != "XLA_FLAGS"})
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"child failed:\n{out.stdout}\n{out.stderr}"
    return out.stdout


def test_exact_psum_topology_invariance():
    run_child("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import exact_accum as EA
from repro.distributed.collectives import exact_psum_tree

x = np.random.default_rng(0).standard_normal((8, 64)).astype(np.float32)
outs = {}
for shape, axes in [((8,), ("data",)), ((4, 2), ("data", "model")),
                    ((2, 4), ("data", "model"))]:
    mesh = jax.make_mesh(shape, axes)
    n = shape[0]

    def f(xl):
        # encode each fixed unit (row), integer-sum locally, integer psum:
        # bitwise identical for ANY replica count / grouping.
        d = EA.encode(xl)                 # (rows_local, 64, L)
        acc = d.sum(0, dtype=jnp.uint32)
        tot = jax.lax.psum(acc, "data")
        return EA.decode(EA.normalize(tot))

    fm = jax.shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P())
    with jax.set_mesh(mesh):
        outs[shape] = np.asarray(fm(jnp.asarray(x)))
# 8-way, 4-way, 2-way reductions of the same data: bitwise identical
ref = outs[(8,)]
for k, v in outs.items():
    assert v.tobytes() == ref.tobytes(), f"mismatch for mesh {k}"
# and equal to the single-host exact reduce
want = np.asarray(EA.exact_reduce(jnp.asarray(x), 1))
assert ref.tobytes() == want.tobytes()
print("OK")
""")


def test_int8_ef_psum():
    run_child("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.distributed.collectives import int8_ef_psum

mesh = jax.make_mesh((8,), ("data",))
x = np.random.default_rng(1).standard_normal((8, 128)).astype(np.float32)

def f(xl, ef):
    m, ef = int8_ef_psum(xl[0], ef[0], "data", 8)
    return m[None], ef[None]

fm = jax.shard_map(f, mesh=mesh, in_specs=(P("data"), P("data")),
               out_specs=(P("data"), P("data")))
ef = jnp.zeros((8, 128), jnp.float32)
with jax.set_mesh(mesh):
    mean, ef = fm(jnp.asarray(x), ef)
mean = np.asarray(mean)[0]
want = x.mean(0)
err1 = np.abs(mean - want).max()
assert err1 < np.abs(x).max() / 127 * 1.01 + 1e-6, err1
# error feedback: repeating the SAME gradient converges toward exact mean
with jax.set_mesh(mesh):
    for _ in range(8):
        mean, ef = fm(jnp.asarray(x), ef)
# time-average of compressed means approaches the true mean; single-shot
# error already bounded; just assert residual stays bounded
assert np.abs(np.asarray(ef)).max() <= np.abs(x).max() / 127 * 1.01
print("OK")
""")


def test_psum_matmul_ring():
    run_child("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.distributed.collectives import psum_matmul_ring

mesh = jax.make_mesh((8,), ("model",))
rng = np.random.default_rng(2)
x = rng.standard_normal((4, 64)).astype(np.float32)
w = rng.standard_normal((64, 32)).astype(np.float32)

def f(xl, wl):
    return psum_matmul_ring(xl, wl, "model", 8)

fm = jax.shard_map(f, mesh=mesh, in_specs=(P(None, "model"), P("model", None)),
               out_specs=P(), check_vma=False)
with jax.set_mesh(mesh):
    out = np.asarray(fm(jnp.asarray(x), jnp.asarray(w)))
np.testing.assert_allclose(out, x @ w, rtol=2e-4, atol=2e-4)
print("OK")
""")


def test_pipeline_parallel_forward():
    run_child("""
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.pipeline import run_pipelined

mesh = jax.make_mesh((4,), ("stage",))
rng = np.random.default_rng(3)
S, D = 4, 16
Ws = rng.standard_normal((S, D, D)).astype(np.float32) * 0.3
x = rng.standard_normal((8, D)).astype(np.float32)

def stage_fn(w, h):
    return jnp.tanh(h @ w)

out = run_pipelined(mesh, stage_fn, jnp.asarray(Ws), jnp.asarray(x),
                    microbatches=4, axis_name="stage")
ref = x
for s in range(S):
    ref = np.tanh(ref @ Ws[s])
np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-4)
print("OK")
""")


def test_elastic_checkpoint_restore_across_meshes():
    run_child("""
import tempfile, jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.train import checkpoint as C

tmp = tempfile.mkdtemp()
mesh8 = jax.make_mesh((8,), ("data",))
x = jnp.arange(8 * 32, dtype=jnp.float32).reshape(8, 32)
xs = jax.device_put(x, NamedSharding(mesh8, P("data", None)))
C.save(tmp, 1, {"w": xs})

mesh4 = jax.make_mesh((2, 4), ("data", "model"))
sh = {"w": NamedSharding(mesh4, P("model", None))}
back, _ = C.restore(f"{tmp}/step_000000001", {"w": x}, shardings=sh)
np.testing.assert_array_equal(np.asarray(back["w"]), np.asarray(x))
assert back["w"].sharding.spec == P("model", None)
print("OK")
""")


def test_reduced_dryrun_on_small_mesh():
    """End-to-end mini dry-run: reduced arch, sharded train_step lower +
    compile + cost analysis on an 8-device mesh."""
    run_child("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs import get_config
from repro.models import build_model
from repro.distributed import sharding as sh
from repro.train import optimizer
from repro.launch.mesh import auto_mesh

mesh = auto_mesh((2, 4), ("data", "model"))
sh.enable_fsdp(mesh)
cfg = get_config("smollm_135m", reduced=True)
model = build_model(cfg)
params_s = jax.eval_shape(model.init, jax.random.key(0))
pspecs = sh.param_pspecs(params_s, mesh)
p_shard = sh.to_shardings(pspecs, mesh)
batch_s = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
           "targets": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
b_shard = sh.to_shardings(sh.batch_pspecs(batch_s, mesh), mesh)
opt_s = jax.eval_shape(optimizer.init, params_s)
o_shard = sh.to_shardings({"m": pspecs, "v": pspecs, "step": P()}, mesh)
ocfg = optimizer.OptConfig()

def train_step(params, opt, batch):
    (loss, metrics), grads = jax.value_and_grad(model.loss, has_aux=True)(params, batch)
    return optimizer.update(ocfg, grads, opt, params)

with jax.set_mesh(mesh):
    co = jax.jit(train_step, in_shardings=(p_shard, o_shard, b_shard),
                 donate_argnums=(0, 1)).lower(params_s, opt_s, batch_s).compile()
c = co.cost_analysis()
assert c["flops"] > 0
print("OK", c["flops"])
""")
