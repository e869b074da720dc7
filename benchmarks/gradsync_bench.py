"""Gradient-sync benchmark: quantized circulant vs int8 ring vs GSPMD.

    PYTHONPATH=src python -m benchmarks.run gradsync

Compares the three gradient synchronisation transports the trainer can
use -- GSPMD 'auto' (f32 ``lax.pmean``), the legacy int8 ring
(``compressed_psum_ring``) and the quantized circulant allreduce
(``circulant_qallreduce_body``) -- and writes ``BENCH_gradsync.json``
at the repo root (committed, so the numbers version with the code).

Committed JSON schema (``schema: 1``; times are medians over iters):

    {
      "schema": 1,
      "note": ...,                    # honest caveat about the testbed
      "model": [                      # analytic, no devices needed
        {"p": ..., "m_bytes": ...,    # payload per rank, f32 bytes
         "rounds_ring": ...,          # 2(p-1)
         "rounds_circulant": ...,     # 2(n-1) + 2 ceil(log2 p)
         "n_blocks": ...,
         "wire_f32_gspmd": ...,       # bytes shipped per rank, f32 ring
         "wire_int8_ring": ...,       # int8 payload + f32 block scales
         "wire_int8_circulant": ...,
         "wire_reduction_vs_f32": ...},
        ...
      ],
      "device": [                     # subprocess, forced host devices
        {"p": ..., "m_bytes": ...,
         "gspmd_auto_us": ...,        # jitted shard_map lax.pmean
         "ring_int8_us": ...,         # compressed_psum_ring w/ EF capture
         "circulant_int8_us": ...,    # circulant_qallreduce_body (jnp)
         "winner": ...},              # fastest of the three, honestly
        ...
      ]
    }

The ``device`` rows come from XLA host devices on one CPU: there is no
real interconnect, so int8-on-the-wire saves no transfer time there and
the quantize/dequantize arithmetic is pure overhead -- GSPMD 'auto'
winning these rows is expected and reported as-is.  The bandwidth claim
of the quantized path lives in the ``model`` rows (4x fewer wire bytes
at the same round count as the f32 circulant schedule); the ``device``
rows bound the compute-side cost of compression and check that the
circulant data plane stays in the same regime as the legacy ring.
"""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_PATH = os.path.join(ROOT, "BENCH_gradsync.json")

CASES = [(8, 262144), (8, 2097152)]  # (p, m_bytes of f32 payload per rank)


def model_rows():
    """Analytic round/wire-volume model -- the actual bandwidth claim."""
    from repro.core.costmodel import DEFAULT_MODEL, optimal_num_blocks_reduce
    from repro.kernels.quant_ops import QBLOCK

    rows = []
    for p, m in CASES:
        elems = m // 4
        n = max(1, optimal_num_blocks_reduce(p, elems, DEFAULT_MODEL))
        n = min(n, max(1, -(-elems // QBLOCK)))
        rounds_ring = 2 * (p - 1)
        rounds_circ = 2 * (n - 1) + 2 * math.ceil(math.log2(p))
        # Bytes shipped per rank: ring reduce-scatter + all-gather each
        # move (p-1) segments of m/p; the circulant schedule moves one
        # block of m/n per round.  int8 payloads carry one f32 scale per
        # QBLOCK elements.
        scale_overhead = 1.0 + 4.0 / QBLOCK
        wire_f32 = 2 * (p - 1) * (m // p)
        wire_ring = int(2 * (p - 1) * (elems // p) * scale_overhead)
        wire_circ = int(rounds_circ * (elems / n) * scale_overhead)
        rows.append({
            "p": p,
            "m_bytes": m,
            "n_blocks": n,
            "rounds_ring": rounds_ring,
            "rounds_circulant": rounds_circ,
            "wire_f32_gspmd": wire_f32,
            "wire_int8_ring": wire_ring,
            "wire_int8_circulant": wire_circ,
            "wire_reduction_vs_f32": round(wire_f32 / wire_circ, 2),
        })
    return rows


_DEVICE_CODE = r"""
import json, time, numpy as np, jax, jax.numpy as jnp
from functools import partial
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map
from repro.core.comm import circulant_qallreduce_body
from repro.optim.compression import compressed_psum_ring

def median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2]

p = len(jax.devices())
mesh = Mesh(np.array(jax.devices()), ("data",))
CASES = %s
rows = []
for pp, m in CASES:
    assert pp == p
    elems = m // 4
    rng = np.random.default_rng(0)
    x = jax.device_put(
        jnp.asarray(rng.standard_normal((p, elems)), jnp.float32),
        NamedSharding(mesh, P("data")))
    sm = partial(shard_map, mesh=mesh, in_specs=P("data"),
                 out_specs=P("data"), check_vma=False)

    @jax.jit
    @sm
    def gspmd_auto(a):
        return jax.lax.pmean(a, "data")

    @jax.jit
    @sm
    def ring_int8(a):
        mean, err = compressed_psum_ring(a[0], "data", p)
        return (mean + 0.0 * err)[None]

    @jax.jit
    @sm
    def circulant_int8(a):
        sums, errs = circulant_qallreduce_body([a[0]], "data", p,
                                               backend="jnp")
        return (sums[0] / p + 0.0 * errs[0])[None]

    row = {"p": p, "m_bytes": m}
    for name, fn in (("gspmd_auto", gspmd_auto), ("ring_int8", ring_int8),
                     ("circulant_int8", circulant_int8)):
        jax.block_until_ready(fn(x))  # compile once
        ts = []
        for _ in range(20):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x))
            ts.append(time.perf_counter() - t0)
        row[name + "_us"] = round(median(ts) * 1e6, 1)
    row["winner"] = min(
        ("gspmd_auto", "ring_int8", "circulant_int8"),
        key=lambda k: row[k + "_us"])
    rows.append(row)
print("JSON" + json.dumps(rows))
"""


def device_rows(p: int = 8):
    """Time the three transports in a subprocess with p host devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={p}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    code = _DEVICE_CODE % repr([(pp, m) for pp, m in CASES if pp == p])
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    if res.returncode != 0:
        raise RuntimeError(res.stderr[-2000:])
    for line in res.stdout.splitlines():
        if line.startswith("JSON"):
            return json.loads(line[4:])
    raise RuntimeError("gradsync device benchmark produced no JSON row")


NOTE = ("device rows are XLA host devices on one CPU (no interconnect): "
        "they bound compression compute overhead only; the bandwidth "
        "claim is the model rows' wire volumes")


def main(write_json: bool = True):
    model = model_rows()
    print("name,p,m_bytes,n_blocks,rounds_ring,rounds_circ,"
          "wire_f32,wire_ring,wire_circ,reduction")
    for r in model:
        print(f"gradsync_model,{r['p']},{r['m_bytes']},{r['n_blocks']},"
              f"{r['rounds_ring']},{r['rounds_circulant']},"
              f"{r['wire_f32_gspmd']},{r['wire_int8_ring']},"
              f"{r['wire_int8_circulant']},{r['wire_reduction_vs_f32']}")
    device = device_rows()
    print("name,p,m_bytes,gspmd_auto_us,ring_int8_us,circulant_int8_us,"
          "winner")
    for r in device:
        print(f"gradsync_device,{r['p']},{r['m_bytes']},"
              f"{r['gspmd_auto_us']},{r['ring_int8_us']},"
              f"{r['circulant_int8_us']},{r['winner']}")
    if write_json:
        payload = {"schema": 1, "note": NOTE, "model": model,
                   "device": device}
        with open(OUT_PATH, "w", encoding="utf-8") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(f"# wrote {os.path.relpath(OUT_PATH, ROOT)}")


if __name__ == "__main__":
    main()
