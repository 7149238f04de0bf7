"""Production training entrypoint.

Wires together: mesh + sharding rules (FSDP/TP/DP) -> model -> trainer
(microbatch accumulation, exact deferred-carry gradient reduction) ->
checkpointing (atomic, signed, async) -> fault tolerance (resume from the
newest valid checkpoint, straggler monitoring).

On this CPU container it drives reduced configs end-to-end (see
examples/train_smollm.py); on a real pod the same file runs the full
configs -- device count and mesh shape are the only changes.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch smollm_135m \
      --reduced --steps 100 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.distributed import sharding as sh
from repro.launch.mesh import auto_mesh
from repro.models import build_model
from repro.train import checkpoint as CKPT
from repro.train import fault_tolerance as FT
from repro.train import optimizer as OPT
from repro.train import trainer as TR


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm_135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-reduce", default="mean",
                    choices=["mean", "exact"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data-axis", type=int, default=0,
                    help="0: use all devices for data parallelism")
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, reduced=args.reduced)
    if args.reduced:
        cfg = cfg.replace(remat="none")
    model = build_model(cfg)

    n_dev = len(jax.devices())
    data_ax = args.data_axis or max(1, n_dev // args.model_axis)
    mesh = auto_mesh((data_ax, args.model_axis), ("data", "model"))
    multi_device = n_dev > 1
    if multi_device:
        sh.enable_fsdp(mesh)

    data = SyntheticLM(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch, seed=0))

    tcfg = TR.TrainerConfig(
        opt=OPT.OptConfig(lr=args.lr, warmup_steps=max(2, args.steps // 20),
                          total_steps=args.steps),
        microbatches=args.microbatches,
        grad_reduce=args.grad_reduce)

    params = model.init(jax.random.key(0))
    opt_state = OPT.init(params)
    start_step = 0

    monitor = FT.StragglerMonitor()
    saver = None
    if args.ckpt_dir:
        rm = FT.RestartManager(args.ckpt_dir)
        step0, state = rm.resume({"params": params, "opt": opt_state})
        if step0 is not None:
            params, opt_state = state["params"], state["opt"]
            start_step = step0 + 1
            print(f"[train] resumed from step {step0}")
        saver = CKPT.AsyncSaver(args.ckpt_dir)

    step_fn = TR.make_train_step(model, tcfg)
    if multi_device:
        pspecs = sh.param_pspecs(jax.eval_shape(lambda: params), mesh)
        p_shard = sh.to_shardings(pspecs, mesh)
        o_shard = sh.to_shardings(
            {"m": pspecs, "v": pspecs, "step": jax.sharding.PartitionSpec()},
            mesh)
        step_fn = jax.jit(step_fn, in_shardings=(p_shard, o_shard, None),
                          out_shardings=(p_shard, o_shard, None),
                          donate_argnums=(0, 1))
        params = jax.device_put(params, p_shard)
        opt_state = jax.device_put(opt_state, o_shard)
    else:
        step_fn = jax.jit(step_fn, donate_argnums=(0, 1))

    t_start = time.time()
    with jax.set_mesh(mesh):
        for step in range(start_step, args.steps):
            monitor.start()
            batch = jax.tree.map(jnp.asarray, data.batch(step))
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            ev = monitor.stop(step)
            if ev:
                print(f"[straggler] step {ev.step}: {ev.ratio:.1f}x median "
                      f"-> {ev.action}")
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"[train] step {step}: loss={float(metrics['loss']):.4f} "
                      f"lr={float(metrics['lr']):.2e} "
                      f"gnorm={float(metrics['grad_norm']):.3f}", flush=True)
            if saver and (step % args.ckpt_every == 0 or step == args.steps - 1):
                saver.save(step, {"params": params, "opt": opt_state})
    if saver:
        saver.wait()
    dt = time.time() - t_start
    tokens = (args.steps - start_step) * args.batch * args.seq
    print(f"[train] done: {dt:.1f}s, {tokens / dt:.0f} tokens/s")
    return params


if __name__ == "__main__":
    main()
