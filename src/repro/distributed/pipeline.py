"""GPipe-style pipeline parallelism via shard_map + collective_permute.

The production mesh fixes (pod, data, model); pipeline stages are an
OPTIONAL alternative mapping of one axis (config `pp_axis`).  Stages hold
contiguous layer groups; microbatches flow through a bubble schedule:

  step t: stage s computes microbatch (t - s) if 0 <= t - s < M,
          then ppermutes its activation to stage s+1.

Communication is one ppermute per step (point-to-point over ICI), which
XLA lowers to async collective-permute -- the compute of step t+1
overlaps the send of step t.  Correctness is tested against the
unpipelined stack on a subprocess mesh (tests/test_distributed.py).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(stage_fn: Callable, n_stages: int, microbatches: int,
                     axis_name: str = "stage"):
    """Build the per-device pipelined forward for shard_map.

    stage_fn(stage_params, x) -> x          (one stage's layer group)
    Returns fn(stage_params_local, x_mb) where x_mb: (M, mb, ...) lives
    fully on stage 0 (other stages receive zeros) and the result is the
    final stage's outputs, broadcast back via ppermute ring closure.
    """

    def fn(stage_params, x_mb):
        # each device's slice of the stacked params keeps a leading dim of 1
        stage_params = jax.tree.map(lambda a: a[0], stage_params)
        sid = jax.lax.axis_index(axis_name)
        M = microbatches
        S = n_stages
        mb_shape = x_mb.shape[1:]
        buf = jnp.zeros(mb_shape, x_mb.dtype)          # current activation
        out = jnp.zeros_like(x_mb)                     # collected outputs
        fwd = [(i, (i + 1) % S) for i in range(S)]

        for t in range(M + S - 1):
            # stage 0 ingests microbatch t (if any)
            if t < M:
                buf = jnp.where(sid == 0, x_mb[t], buf)
            y = stage_fn(stage_params, buf)
            # last stage records its finished microbatch (t - (S-1))
            rec = t - (S - 1)
            if 0 <= rec < M:
                out = jnp.where(sid == S - 1,
                                out.at[rec].set(y), out)
            # shift activations to the next stage
            buf = jax.lax.ppermute(y, axis_name, fwd)
        # broadcast final outputs from the last stage to everyone
        out = jax.lax.psum(
            jnp.where(sid == S - 1, out, jnp.zeros_like(out)), axis_name)
        return out

    return fn


def run_pipelined(mesh: Mesh, stage_fn, stage_params_stacked, x,
                  microbatches: int, axis_name: str = "stage"):
    """stage_params_stacked: (S, ...) pytree; x: (batch, ...) on host.
    Splits batch into microbatches, shard_maps over the stage axis."""
    S = mesh.shape[axis_name]
    B = x.shape[0]
    assert B % microbatches == 0
    x_mb = x.reshape(microbatches, B // microbatches, *x.shape[1:])

    fn = pipeline_forward(stage_fn, S, microbatches, axis_name)
    mapped = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(axis_name), P()),      # params sharded by stage
        out_specs=P(),
    )
    with jax.set_mesh(mesh):
        out_mb = mapped(stage_params_stacked, x_mb)
    return out_mb.reshape(B, *x.shape[1:])
