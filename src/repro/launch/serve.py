"""Production serving launcher: sharded prefill + batched decode on a mesh.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve \
        --arch qwen2-0.5b --smoke --mesh 4x2 --batch 8 --steps 16

Weights are TP-sharded over 'model' and (per the D2 finding in
EXPERIMENTS.md) replicated over 'data'; the KV cache shards batch over
'data' and heads/seq over 'model' per train/sharding.py rules.
"""

import os
import sys

if __name__ == "__main__" and "--devices" in sys.argv:
    n = sys.argv[sys.argv.index("--devices") + 1]
    os.environ.setdefault("XLA_FLAGS", f"--xla_force_host_platform_device_count={n}")

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.models import hints
from repro.models.transformer import decode_step, init_cache, init_params
from repro.train.sharding import cache_pspecs, mesh_axes, named, param_pspecs


def build_mesh(spec: str) -> Mesh:
    dims = [int(x) for x in spec.split("x")]
    devs = jax.devices()
    need = int(np.prod(dims))
    assert len(devs) >= need
    names = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
    return Mesh(np.array(devs[:need]).reshape(dims), names)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mesh", default="2x2")
    ap.add_argument("--devices", default=None)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--steps", type=int, default=16)
    args = ap.parse_args()

    mesh = build_mesh(args.mesh)
    dp_axes, model_axis = mesh_axes(mesh)
    jax.sharding.set_mesh(mesh)
    hints.set_hint("hidden", P(dp_axes, None, None))
    cfg = get_config(args.arch, smoke=args.smoke)
    print(f"mesh {dict(mesh.shape)}  model {cfg.name}")

    params = init_params(cfg, jax.random.PRNGKey(0))
    pspecs = param_pspecs(cfg, params, mesh, no_fsdp=True)  # serving: no ZeRO
    params = jax.device_put(params, named(mesh, pspecs))

    cache = init_cache(cfg, args.batch, args.max_seq)
    cspecs = cache_pspecs(cfg, mesh, cache)
    cache = jax.device_put(cache, named(mesh, cspecs))

    step = jax.jit(lambda p, c, t: decode_step(p, cfg, c, t),
                   donate_argnums=(1,))
    tok = jnp.ones((args.batch, 1), jnp.int32)
    # warmup + timed decode
    logits, cache = step(params, cache, tok)
    jax.block_until_ready(logits)
    t0 = time.time()
    for i in range(args.steps):
        logits, cache = step(params, cache, tok)
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    jax.block_until_ready(logits)
    dt = time.time() - t0
    tput = args.batch * args.steps / dt
    print(f"{args.steps} decode steps, batch {args.batch}: "
          f"{dt/args.steps*1e3:.1f} ms/step, {tput:.1f} tok/s")
    assert bool(jnp.isfinite(logits).all())
    print("OK")


if __name__ == "__main__":
    main()
