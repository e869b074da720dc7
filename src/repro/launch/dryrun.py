import os
os.environ["XLA_FLAGS"] = os.environ.get("DRYRUN_XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell against the production mesh with ShapeDtypeStruct stand-ins
(no device allocation), and record the roofline inputs:

    python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k --mesh single
    python -m repro.launch.dryrun --arch all --shape all --mesh both --subproc

Per cell this prints/saves:
  * compiled.memory_analysis()   -- proves the cell fits per-device HBM,
  * compiled.cost_analysis()     -- per-device HLO FLOPs / bytes accessed,
  * parsed collective stats      -- per-device collective bytes + rounds,
  * derived roofline terms (see repro/launch/roofline.py).

NOTE: the XLA_FLAGS line above must execute before ANY jax import (jax
locks the device count on first init); keep it the first statement.
"""

import argparse
import json
import subprocess
import sys
import time
import traceback
from dataclasses import asdict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import all_arch_names, get_config
from repro.launch.hlo_analysis import collective_stats, weighted_cost
from repro.launch.mesh import make_production_mesh
from repro.models.common import SHAPES, ModelConfig, ShapeConfig
from repro.models import moe as moe_mod
from repro.models.transformer import init_cache
from repro.optim.adamw import AdamWConfig
from repro.serve.engine import make_decode_step, make_prefill_step
from repro.train.sharding import batch_pspecs, cache_pspecs, mesh_axes, named, param_pspecs
from repro.train.trainer import TrainConfig, make_train_step, train_state_shape

from jax.sharding import NamedSharding, PartitionSpec as P

# long_500k requires sub-quadratic attention: run for ssm/hybrid/SWA archs.
LONG_OK = {"zamba2-2.7b", "mamba2-780m", "h2o-danube-1.8b"}

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "results", "dryrun")


def default_microbatches(cfg: ModelConfig, shape: ShapeConfig, dp: int) -> int:
    if shape.kind != "train":
        return 1
    per_dev = max(1, shape.global_batch // dp)
    if cfg.d_model >= 4096 or cfg.moe is not None:
        target = 1
    elif cfg.d_model >= 2048:
        target = 2
    else:
        target = 4
    return max(1, per_dev // target)


def batch_shapes(cfg: ModelConfig, shape: ShapeConfig):
    gb, s = shape.global_batch, shape.seq_len
    out = {
        "tokens": jax.ShapeDtypeStruct((gb, s), jnp.int32),
        "labels": jax.ShapeDtypeStruct((gb, s), jnp.int32),
    }
    if cfg.family == "vlm":
        out["memory_embeds"] = jax.ShapeDtypeStruct(
            (gb, cfg.n_image_tokens, cfg.d_model), jnp.float32
        )
    if cfg.family == "encdec":
        out["memory_embeds"] = jax.ShapeDtypeStruct(
            (gb, cfg.n_audio_frames, cfg.d_model), jnp.float32
        )
    return out


def input_specs(arch: str, shape_name: str):
    """Public helper: ShapeDtypeStruct stand-ins for every model input of
    the given cell (the dry-run contract)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape.kind in ("train", "prefill"):
        return batch_shapes(cfg, shape)
    cache = jax.eval_shape(
        lambda: init_cache(
            cfg, shape.global_batch, shape.seq_len,
            memory=_memory_shape(cfg, shape),
        )
    )
    return {
        "tokens": jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32),
        "cache": cache,
    }


def _memory_shape(cfg, shape):
    if cfg.family == "vlm":
        return jnp.zeros((shape.global_batch, cfg.n_image_tokens, cfg.d_model), jnp.float32)
    if cfg.family == "encdec":
        return jnp.zeros((shape.global_batch, cfg.n_audio_frames, cfg.d_model), jnp.float32)
    return None


def _infer_no_fsdp(cfg: ModelConfig, mesh, model_axis: str) -> bool:
    """Replicate inference weights over dp only when the TP-sharded copy
    is small (<= 2 GB/device) and the model is not MoE (expert weights
    dominate HBM; deepseek-v3's 84 GB/device copy obviously cannot be
    replicated).  Saves ~2 GB/token of ZeRO-3 weight re-gather on the
    cells where it fits (EXPERIMENTS.md Perf D2)."""
    if os.environ.get("DRYRUN_INFER_NO_FSDP", "1") != "1":
        return False
    per_dev = cfg.param_count() * 2 / mesh.shape[model_axis]
    return per_dev <= 2e9 and cfg.moe is None


def lower_cell(arch: str, shape_name: str, multi_pod: bool, microbatches=None,
               remat: str = "full", extra_tag: str = ""):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    dp_axes, model_axis = mesh_axes(mesh)
    dp = int(np.prod([mesh.shape[a] for a in dp_axes]))
    jax.sharding.set_mesh(mesh)
    from repro.train import sharding as shard_rules
    ep_mode = os.environ.get("DRYRUN_EP_MODE", "2d")
    shard_rules.set_ep_mode(ep_mode)
    shard_rules.set_cache_seq_shard(
        os.environ.get("DRYRUN_CACHE_SEQ_SHARD", "1") == "1")
    if ep_mode == "full":
        moe_mod.set_default_ep_spec(P(shard_rules.ep_axes(mesh), None, None))
    else:
        moe_mod.set_default_ep_spec(P(model_axis, None, None))
    from repro.models import hints
    hints.set_hint("hidden", P(dp_axes, None, None))
    hints.set_hint("logits", P(dp_axes, None, model_axis))
    if os.environ.get("DRYRUN_ATTN_SHARD", "1") == "1":
        # q heads over 'model' (GSPMD pads uneven counts); kv heads only
        # when they divide the axis -- padding 8 kv heads to 16 shards
        # was measured 5x WORSE on stablelm (see EXPERIMENTS.md Perf C1),
        # replicated kv heads are tiny and keep scores fully local.
        msize = mesh.shape[model_axis]
        hints.set_hint("attn_q", P(dp_axes, None, model_axis, None))
        kv_ok = cfg.n_kv_heads and cfg.n_kv_heads % msize == 0
        hints.set_hint(
            "attn_kv",
            P(dp_axes, None, model_axis if kv_ok else None, None),
        )

    if shape.name == "long_500k" and arch not in LONG_OK:
        return {
            "arch": arch, "shape": shape_name,
            "mesh": "multi" if multi_pod else "single",
            "skipped": f"{arch} is full-attention; long_500k requires "
            "sub-quadratic attention (see DESIGN.md)",
        }

    mb = microbatches or default_microbatches(cfg, shape, dp)
    t0 = time.time()

    if shape.kind == "train":
        big = cfg.param_count() > 5e10
        tcfg = TrainConfig(
            microbatches=mb, remat=remat,
            opt=AdamWConfig(moment_dtype="bfloat16" if big else "float32"),
            grad_acc_dtype="bfloat16" if big else "float32",
            dp_axes=dp_axes,
        )
        state_shape = train_state_shape(cfg, tcfg)
        pspecs = param_pspecs(cfg, state_shape["params"], mesh)
        state_specs = {
            "params": pspecs,
            "opt": {"mu": pspecs, "nu": pspecs, "step": P()},
        }
        bshapes = batch_shapes(cfg, shape)
        bspecs = batch_pspecs(cfg, mesh, bshapes)
        step = make_train_step(cfg, tcfg)
        jitted = jax.jit(
            step,
            in_shardings=(named(mesh, state_specs), named(mesh, bspecs)),
            donate_argnums=(0,),
        )
        lowered = jitted.lower(state_shape, bshapes)
    elif shape.kind == "prefill":
        no_fsdp = _infer_no_fsdp(cfg, mesh, model_axis)
        state_shape = jax.eval_shape(
            lambda k: __import__("repro.models.transformer", fromlist=["init_params"]).init_params(cfg, k),
            jax.random.PRNGKey(0),
        )
        pspecs = param_pspecs(cfg, state_shape, mesh, no_fsdp=no_fsdp)
        bshapes = batch_shapes(cfg, shape)
        bspecs = batch_pspecs(cfg, mesh, bshapes)
        pre = make_prefill_step(cfg)

        def prefill_fn(params, tokens, memory_embeds=None):
            return pre(params, tokens, memory_embeds)

        args = [state_shape, bshapes["tokens"]]
        in_sh = [named(mesh, pspecs), named(mesh, bspecs["tokens"])]
        if "memory_embeds" in bshapes:
            args.append(bshapes["memory_embeds"])
            in_sh.append(named(mesh, bspecs["memory_embeds"]))
        jitted = jax.jit(prefill_fn, in_shardings=tuple(in_sh))
        lowered = jitted.lower(*args)
    else:  # decode
        from repro.models.transformer import init_params

        no_fsdp = _infer_no_fsdp(cfg, mesh, model_axis)
        state_shape = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
        pspecs = param_pspecs(cfg, state_shape, mesh, no_fsdp=no_fsdp)
        cache_shape = jax.eval_shape(
            lambda: init_cache(cfg, shape.global_batch, shape.seq_len,
                               memory=_memory_shape(cfg, shape))
        )
        cspecs = cache_pspecs(cfg, mesh, cache_shape)
        tok = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
        tok_spec = P(dp_axes if shape.global_batch >= dp else None, None)
        dec = make_decode_step(cfg)
        jitted = jax.jit(
            dec,
            in_shardings=(named(mesh, pspecs), named(mesh, cspecs),
                          NamedSharding(mesh, tok_spec)),
            donate_argnums=(1,),
        )
        lowered = jitted.lower(state_shape, cache_shape, tok)

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    txt = compiled.as_text()
    coll = collective_stats(txt)
    wc = weighted_cost(txt)

    # analytic model flops for the "useful compute" ratio
    n_active = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    passes = 6 if shape.kind == "train" else 2
    model_flops_per_dev = passes * n_active * tokens / int(
        np.prod(list(mesh.shape.values()))
    )

    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "tag": extra_tag,
        "microbatches": mb,
        "remat": remat,
        "devices": int(np.prod(list(mesh.shape.values()))),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "flops_per_device": float(ca.get("flops", 0.0)),
        "bytes_per_device": float(ca.get("bytes accessed", 0.0)),
        "flops_weighted": float(wc["flops_weighted"]),
        "bytes_weighted": float(wc["bytes_weighted"]),
        "model_flops_per_device": float(model_flops_per_dev),
        "params_total": int(cfg.param_count()),
        "params_active": int(n_active),
        "memory": {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_estimate_bytes": int(
                ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes
            ),
        },
        **coll.as_dict(),
    }
    return rec


def cell_path(arch, shape, meshkind, tag=""):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    sfx = f"_{tag}" if tag else ""
    return os.path.join(RESULTS_DIR, f"{arch}__{shape}__{meshkind}{sfx}.json")


def run_cell(arch, shape, meshkind, microbatches=None, remat="full", tag=""):
    rec = lower_cell(arch, shape, meshkind == "multi", microbatches, remat, tag)
    path = cell_path(arch, shape, meshkind, tag)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps(rec, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--remat", default="full")
    ap.add_argument("--tag", default="")
    ap.add_argument("--subproc", action="store_true",
                    help="one subprocess per cell (fresh XLA heap)")
    ap.add_argument("--skip-done", action="store_true")
    args = ap.parse_args()

    archs = all_arch_names() if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for meshkind in meshes:
                if args.skip_done and os.path.exists(cell_path(arch, shape, meshkind, args.tag)):
                    print(f"skip done: {arch} {shape} {meshkind}")
                    continue
                print(f"=== {arch} x {shape} x {meshkind} ===", flush=True)
                if args.subproc:
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", arch, "--shape", shape, "--mesh", meshkind,
                           "--remat", args.remat]
                    if args.microbatches:
                        cmd += ["--microbatches", str(args.microbatches)]
                    if args.tag:
                        cmd += ["--tag", args.tag]
                    r = subprocess.run(cmd)
                    if r.returncode != 0:
                        failures.append((arch, shape, meshkind))
                else:
                    try:
                        run_cell(arch, shape, meshkind, args.microbatches,
                                 args.remat, args.tag)
                    except Exception:
                        traceback.print_exc()
                        failures.append((arch, shape, meshkind))
    if failures:
        print("FAILURES:", failures)
        sys.exit(1)
    print("dry-run complete")


if __name__ == "__main__":
    main()
