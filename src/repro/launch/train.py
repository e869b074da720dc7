"""Production training launcher: mesh + sharded state + fault tolerance.

    # real pod (or host-device simulation of one):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.train \
        --arch qwen2-0.5b --smoke --mesh 4x2 --steps 20

Assembles every substrate layer on an explicit (data, model) mesh:
sharded train state (ZeRO-3 + TP rules from train/sharding.py), the
deterministic data pipeline sharded over the data axis, jit with
in/out shardings and state donation, checkpoint/auto-resume, and the
paper's circulant broadcast for the restore fan-out when more than one
data shard participates.
"""

import os
import sys

if __name__ == "__main__" and "--devices" in sys.argv:
    n = sys.argv[sys.argv.index("--devices") + 1]
    os.environ.setdefault("XLA_FLAGS", f"--xla_force_host_platform_device_count={n}")

import argparse
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models import hints
from repro.optim.adamw import AdamWConfig
from repro.train.checkpoint import CheckpointManager
from repro.train.sharding import batch_pspecs, mesh_axes, named, param_pspecs
from repro.train.trainer import TrainConfig, init_train_state, make_train_step


def build_mesh(spec: str) -> Mesh:
    dims = [int(x) for x in spec.split("x")]
    devs = jax.devices()
    need = int(np.prod(dims))
    assert len(devs) >= need, f"need {need} devices, have {len(devs)}"
    names = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
    return Mesh(np.array(devs[:need]).reshape(dims), names)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--mesh", default="2x2", help="e.g. 4x2 = data4 x model2")
    ap.add_argument("--devices", default=None, help="host device count")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--grad-sync", default="auto",
                    choices=("auto", "compressed"),
                    help="'compressed' = int8 quantized circulant "
                         "allreduce with error feedback (pure-dp mesh)")
    ap.add_argument("--grad-sync-backend", default="jnp",
                    choices=("jnp", "pallas"))
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    args = ap.parse_args()

    mesh = build_mesh(args.mesh)
    dp_axes, model_axis = mesh_axes(mesh)
    dp = int(np.prod([mesh.shape[a] for a in dp_axes]))
    jax.sharding.set_mesh(mesh)
    if args.grad_sync == "auto":
        # GSPMD layout hints.  The compressed path runs the model inside
        # shard_map (every mesh axis manual), where sharding constraints
        # are both illegal and pointless -- shards are explicit already.
        hints.set_hint("hidden", P(dp_axes, None, None))
        hints.set_hint("logits", P(dp_axes, None, model_axis))
    print(f"mesh {dict(mesh.shape)}  dp={dp}")

    cfg = get_config(args.arch, smoke=args.smoke)
    microbatches = args.microbatches
    if args.grad_sync == "compressed":
        # The compressed step microbatches the per-rank shard (the model
        # runs inside shard_map), so the split must divide batch/dp.
        local = max(1, args.global_batch // dp)
        microbatches = math.gcd(microbatches, local)
        if microbatches != args.microbatches:
            print(f"grad-sync=compressed: microbatches "
                  f"{args.microbatches} -> {microbatches} "
                  f"(must divide per-rank batch {local})")
    tcfg = TrainConfig(
        microbatches=microbatches, remat="full",
        opt=AdamWConfig(lr=args.lr, warmup_steps=5, total_steps=args.steps),
        dp_axes=dp_axes,
        grad_sync=args.grad_sync,
        grad_sync_backend=args.grad_sync_backend,
    )
    print(f"model {cfg.name}: {cfg.param_count()/1e6:.1f}M params")

    # sharded state
    state = init_train_state(cfg, tcfg, jax.random.PRNGKey(0), mesh=mesh)
    pspecs = param_pspecs(cfg, state["params"], mesh)
    state_specs = {"params": pspecs,
                   "opt": {"mu": pspecs, "nu": pspecs, "step": P()}}
    if "gsync_err" in state:
        # error-feedback buckets: [dp, bucket] rows, one per dp shard
        state_specs["gsync_err"] = tuple(
            P(dp_axes) for _ in state["gsync_err"])
    state = jax.device_put(state, named(mesh, state_specs))

    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                  global_batch=args.global_batch))
    bshapes = data.batch_at(0)
    bspecs = batch_pspecs(cfg, mesh, {
        k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in bshapes.items()
    })
    bnamed = named(mesh, bspecs)

    # Pin the output state to the same ZeRO-3/TP specs as the input:
    # without out_shardings GSPMD may pick a different layout for some
    # leaves after step 1, which then mismatches in_shardings (and
    # silently drifts the state layout on any jax version).
    step_fn = jax.jit(
        make_train_step(cfg, tcfg, mesh=mesh),
        in_shardings=(named(mesh, state_specs), bnamed),
        out_shardings=(named(mesh, state_specs), None),
        donate_argnums=(0,),
    )

    mgr = CheckpointManager(args.ckpt_dir, keep=2)
    start, state_restored, extra = mgr.restore_latest(
        jax.tree.map(np.asarray, state))
    t0_step = 0
    if start is not None:
        state = jax.device_put(state_restored, named(mesh, state_specs))
        t0_step = int(extra.get("data_step", 0))
        print(f"resumed from step {start}")

    t0 = time.time()
    for i in range(t0_step, args.steps):
        batch = jax.device_put(data.batch_at(i), bnamed)
        state, m = step_fn(state, batch)
        if (i + 1) % 5 == 0:
            print(f"step {i+1:4d}  loss {float(m['loss']):.4f}  "
                  f"gnorm {float(m['grad_norm']):.3f}")
        if (i + 1) % args.ckpt_every == 0:
            mgr.save(i + 1, jax.tree.map(np.asarray, state),
                     extra={"data_step": i + 1})
    mgr.wait()
    dt = time.time() - t0
    print(f"done: {args.steps - t0_step} steps in {dt:.1f}s "
          f"({dt/max(args.steps-t0_step,1)*1e3:.0f} ms/step)")


if __name__ == "__main__":
    main()
