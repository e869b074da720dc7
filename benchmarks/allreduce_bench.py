"""Reversed-schedule family: reduction / all-reduction benchmarks.

Two complementary measurements (no real cluster in this container):

  1. alpha-beta model sweep over message size m at the paper's cluster
     size p = 36*32 = 1152: the circulant all-reduction (reversed reduce
     + forward broadcast, 2(n-1)+2q rounds) with the analytically
     optimal n vs ring all-reduce (2(p-1) rounds, bandwidth-optimal) vs
     recursive doubling (q rounds of the full message) vs binomial
     reduce + broadcast.
  2. wall-clock on host devices (subprocess, p=8): the JAX
     circulant_allreduce vs XLA's native psum path, microseconds/call.
"""

from __future__ import annotations

import os
import subprocess
import sys

from repro.core.costmodel import (
    CommModel,
    allreduce_circulant_cost,
    allreduce_recursive_doubling_cost,
    allreduce_ring_cost,
    bcast_binomial_cost,
    optimal_num_blocks_allreduce,
    reduce_binomial_cost,
)
from repro.core.engine import get_bundle

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

P_CLUSTER = 36 * 32
SIZES = [1 << k for k in range(6, 27, 2)]  # 64 B .. 64 MB


def model_rows(p: int = P_CLUSTER, model: CommModel = CommModel(alpha=2e-6, beta=1 / 10e9)):
    # Forward AND reversed phases come from this one cached bundle.
    bundle = get_bundle(p)
    rows = []
    for m in SIZES:
        n = optimal_num_blocks_allreduce(p, m, model)
        rows.append({
            "m": m,
            "n_opt": n,
            "rounds": bundle.allreduce_rounds(max(1, n)),
            "circulant_us": 1e6 * allreduce_circulant_cost(p, m, n, model),
            "ring_us": 1e6 * allreduce_ring_cost(p, m, model),
            "recdoub_us": 1e6 * allreduce_recursive_doubling_cost(p, m, model),
            "binomial_us": 1e6 * (reduce_binomial_cost(p, m, model)
                                  + bcast_binomial_cost(p, m, model)),
        })
    return rows


def wallclock_rows(p: int = 8):
    """Run the host-device wall-clock benchmark in a subprocess."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={p}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + os.pathsep + env.get("PYTHONPATH", "")
    code = r"""
import time, numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.core.collectives import circulant_allreduce
from jax import shard_map
p = len(jax.devices())
mesh = Mesh(np.array(jax.devices()), ("data",))
def native_psum(a):
    return shard_map(lambda xs: jax.lax.psum(xs, "data"),
                     mesh=mesh, in_specs=P("data"), out_specs=P(),
                     check_vma=False)(a)
for m in (1024, 65536, 1048576):
    elems = m // 4
    x = jax.device_put(jnp.ones((p, elems), jnp.float32), NamedSharding(mesh, P("data")))
    for name, fn in [
        ("circulant_n1", lambda a: circulant_allreduce(mesh, "data", a, n_blocks=1)),
        ("circulant_nopt", lambda a: circulant_allreduce(mesh, "data", a)),
        ("native_psum", native_psum),
    ]:
        f = jax.jit(fn)
        jax.tree.leaves(f(x))[0].block_until_ready()
        t0 = time.perf_counter(); it = 20
        for _ in range(it):
            r = f(x)
            jax.tree.leaves(r)[0].block_until_ready()
        dt = (time.perf_counter() - t0) / it
        print(f"WC,{name},{m},{dt*1e6:.1f}")
"""
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    rows = []
    for line in res.stdout.splitlines():
        if line.startswith("WC,"):
            _, name, m, us = line.split(",")
            rows.append({"impl": name, "m": int(m), "us": float(us)})
    if res.returncode != 0:
        raise RuntimeError(res.stderr[-2000:])
    return rows


def roundstep_main(p: int = 8, n: int = 8):
    """jnp-vs-pallas timing of one fused reduce round step (the
    accumulate+capture/drain, op="sum"); shared sweep in
    ``roundstep_common``."""
    from benchmarks.roundstep_common import roundstep_main as rs_main

    rs_main("allreduce", p=p, n=n)


def main():
    print("name,m_bytes,n_opt,rounds,circulant_us,ring_us,recdoub_us,binomial_us")
    for r in model_rows():
        print(f"allreduce_model,{r['m']},{r['n_opt']},{r['rounds']},"
              f"{r['circulant_us']:.1f},{r['ring_us']:.1f},"
              f"{r['recdoub_us']:.1f},{r['binomial_us']:.1f}")
    print("name,impl,m_bytes,us_per_call")
    for r in wallclock_rows():
        print(f"allreduce_wallclock,{r['impl']},{r['m']},{r['us']:.1f}")


if __name__ == "__main__":
    main()
