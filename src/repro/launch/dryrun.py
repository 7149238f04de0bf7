"""Multi-pod dry run: lower + compile every (arch x shape x mesh) cell.

For each cell this lowers the real step function (train_step with AdamW
update for train shapes; prefill / serve_step for inference shapes) with
production shardings, compiles it, and records:
  * compiled.memory_analysis()  -- proves the cell fits per-device HBM
  * compiled.cost_analysis()    -- HLO FLOPs / bytes for the roofline
  * collective bytes parsed from the post-SPMD HLO text
  * analytic MODEL_FLOPS (6*N*D train / 2*N_active*D serve)

Variants: "base" uses the default layer-scan segmentation; "split" adds
one extra scan over the same layers so roofline.py can isolate the
scan-body cost (cost_analysis counts loop bodies once).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite_3_8b \
      --shape train_4k --mesh single --variant base --out experiments/dryrun
  PYTHONPATH=src python -m repro.launch.dryrun --all  # full sweep, resumable
"""
import argparse
import json
import os
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.distributed import sharding as sh
from repro.launch import hlo_stats
from repro.launch.mesh import make_production_mesh
from repro.models import SHAPES_BY_NAME, applicable_shapes, build_model
from repro.train import optimizer


# ---------------------------------------------------------------------------
# analytic FLOPs (roofline denominator sanity): 6*N*D (dense train),
# 6*N_active*D (MoE train), 2*N_active per generated token (serve).
# ---------------------------------------------------------------------------

def count_params(tree) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(tree))


def active_params(cfg, params_shapes) -> int:
    total = count_params(params_shapes)
    if cfg.num_experts == 0:
        return total
    expert = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params_shapes)[0]:
        p = "/".join(str(getattr(k, "key", k)) for k in path)
        if "moe/w" in p:
            expert += int(np.prod(leaf.shape))
    return total - expert + expert * cfg.top_k // cfg.num_experts


def model_flops(cfg, shape, params_shapes) -> float:
    n_act = active_params(cfg, params_shapes)
    if shape.kind == "train":
        tokens = shape.seq_len * shape.global_batch
        return 6.0 * n_act * tokens
    if shape.kind == "prefill":
        tokens = shape.seq_len * shape.global_batch
        return 2.0 * n_act * tokens
    return 2.0 * n_act * shape.global_batch      # decode: one token / seq


# ---------------------------------------------------------------------------
# segment variants for the scan-body cost extraction
# ---------------------------------------------------------------------------

def segment_variants(cfg):
    """Returns {variant_name: segments_arg}, where segments_arg feeds
    build_model(cfg, segments=...)."""
    model = build_model(cfg)
    info = model.scan_info()
    out = {"base": None}

    def split_first(segs):
        segs = list(segs)
        for i, s in enumerate(segs):
            if s >= 2:
                return tuple(segs[:i] + [s - 1, 1] + segs[i + 1:])
        return tuple(segs)

    if cfg.family == "audio":
        enc_u, enc_segs = info["enc"]
        dec_u, dec_segs = info["dec"]
        out["split_enc"] = {"enc": split_first(enc_segs), "dec": dec_segs}
        out["split_dec"] = {"enc": enc_segs, "dec": split_first(dec_segs)}
    else:
        units, segs = info["layers"]
        out["split"] = split_first(segs)
    return out


# ---------------------------------------------------------------------------
# cell construction + compile
# ---------------------------------------------------------------------------

def lower_cell(arch: str, shape_name: str, mesh_kind: str, variant: str):
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    if shape.kind in ("prefill", "decode"):
        # serving runs on bf16 weights: halves weight reads + FSDP gather
        # traffic in the memory-bound decode regime (SSPerf cell 3, iter 1)
        cfg = cfg.replace(param_dtype="bfloat16")
    # decode with kv_heads < TP: row-parallel attention + seq-sharded cache
    sh.set_attn_row_parallel(
        shape.kind == "decode" and cfg.num_kv_heads > 0
        and cfg.num_kv_heads % 16 != 0)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    segments = segment_variants(cfg)[variant]
    model = build_model(cfg, segments=segments)

    params_s = jax.eval_shape(model.init, jax.random.key(0))
    pspecs = sh.param_pspecs(params_s, mesh)
    sh.enable_fsdp(mesh)
    p_shard = sh.to_shardings(pspecs, mesh)
    batch_s = model.input_specs(shape)
    b_shard = sh.to_shardings(sh.batch_pspecs(batch_s, mesh), mesh)

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt_s = jax.eval_shape(optimizer.init, params_s)
            o_pspec = {"m": pspecs, "v": pspecs,
                       "step": jax.sharding.PartitionSpec()}
            o_shard = sh.to_shardings(o_pspec, mesh)
            opt_cfg = optimizer.OptConfig()

            def train_step(params, opt, batch):
                (loss, metrics), grads = jax.value_and_grad(
                    model.loss, has_aux=True)(params, batch)
                params, opt, om = optimizer.update(opt_cfg, grads, opt, params)
                return params, opt, {"loss": loss, **metrics, **om}

            fn = jax.jit(train_step,
                         in_shardings=(p_shard, o_shard, b_shard),
                         out_shardings=(p_shard, o_shard, None),
                         donate_argnums=(0, 1))
            lowered = fn.lower(params_s, opt_s, batch_s)
        elif shape.kind == "prefill":
            cache_s = model.cache_specs(shape.global_batch, shape.seq_len)
            c_shard = sh.to_shardings(
                sh.cache_pspecs(cache_s, mesh, shape.global_batch,
                                shape.seq_len), mesh)

            def prefill_step(params, batch, cache):
                return model.prefill(params, batch, cache)

            fn = jax.jit(prefill_step,
                         in_shardings=(p_shard, b_shard, c_shard),
                         donate_argnums=(2,))
            lowered = fn.lower(params_s, batch_s, cache_s)
        else:  # decode
            cache_s = model.cache_specs(shape.global_batch, shape.seq_len)
            c_shard = sh.to_shardings(
                sh.cache_pspecs(cache_s, mesh, shape.global_batch,
                                shape.seq_len), mesh)
            tok_s = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
            t_shard = sh.to_shardings(
                sh.batch_pspecs(tok_s, mesh), mesh)
            idx_s = jax.ShapeDtypeStruct((), jnp.int32)

            def serve_step(params, cache, tokens, index):
                return model.decode_step(params, cache, tokens, index)

            fn = jax.jit(serve_step,
                         in_shardings=(p_shard, c_shard, t_shard, None),
                         donate_argnums=(1,))
            lowered = fn.lower(params_s, cache_s, tok_s, idx_s)
    return cfg, shape, params_s, lowered


def run_cell(arch, shape_name, mesh_kind, variant, out_dir,
             keep_hlo: bool = False):
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "variant": variant, "ok": False}
    t0 = time.time()
    try:
        cfg, shape, params_s, lowered = lower_cell(
            arch, shape_name, mesh_kind, variant)
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)
        mem = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
        }
        cost = compiled.cost_analysis()
        rec["cost"] = {"flops": cost.get("flops", 0.0),
                       "bytes_accessed": cost.get("bytes accessed", 0.0)}
        txt = compiled.as_text()
        rec["collectives"] = hlo_stats.collective_bytes(txt)
        rec["hlo_lines"] = txt.count("\n")
        rec["params"] = count_params(params_s)
        rec["active_params"] = active_params(cfg, params_s)
        rec["model_flops"] = model_flops(cfg, shape, params_s)
        model = build_model(cfg)
        rec["scan_info"] = {k: [v[0], list(v[1])]
                            for k, v in model.scan_info().items()}
        rec["ok"] = True
        if keep_hlo:
            (out_dir / f"{arch}.{shape_name}.{mesh_kind}.{variant}.hlo.txt"
             ).write_text(txt)
    except Exception as e:  # noqa: BLE001 - record and continue the sweep
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.time() - t0, 2)
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"{arch}.{shape_name}.{mesh_kind}.{variant}.json"
    path.write_text(json.dumps(rec, indent=1))
    status = "OK" if rec["ok"] else f"FAIL ({rec.get('error', '')[:120]})"
    print(f"[dryrun] {arch} {shape_name} {mesh_kind} {variant}: {status} "
          f"({rec['total_s']}s)", flush=True)
    return rec


def enumerate_cells(mesh_kinds=("single", "multi"), variants_on="single"):
    """Full sweep: every (arch x applicable shape x mesh); segment-split
    variants only on the roofline (single-pod) mesh."""
    cells = []
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in applicable_shapes(cfg):
            for mesh_kind in mesh_kinds:
                cells.append((arch, shape.name, mesh_kind, "base"))
                if mesh_kind == variants_on:
                    for v in segment_variants(cfg):
                        if v != "base":
                            cells.append((arch, shape.name, mesh_kind, v))
    return cells


def main():
    # 512 fake host devices for the production meshes.  Set here, not at
    # import, so importing this module never changes the process's
    # devices; it must run before jax initializes its first backend.
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--variant", default="base")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--keep-hlo", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()
    out_dir = pathlib.Path(args.out)

    if args.all:
        cells = enumerate_cells()
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape, args.mesh, args.variant)]

    n_fail = 0
    for cell in cells:
        path = out_dir / ("%s.%s.%s.%s.json" % cell)
        if args.skip_existing and path.exists():
            rec = json.loads(path.read_text())
            if rec.get("ok"):
                continue
        rec = run_cell(*cell, out_dir=out_dir, keep_hlo=args.keep_hlo)
        n_fail += 0 if rec["ok"] else 1
    print(f"[dryrun] done; {n_fail} failures")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
