#!/usr/bin/env python3
"""Bring-up smoke test of the circulant collectives on TPU chips.

    python chip_smoke.py [--seed 0]           # one chip (the default)
    python chip_smoke.py --chips 4 [--seed 0] # one four-chip host

One chip has no second rank to exchange with, so the default phase runs
the library's per-round data plane through ``repro.core.comm.host_plan``:
the p ranks sit on the leading array axis and the exchange is a row
rotation.  It covers every host-plan kind at deployment sizes on both
round-step backends:

  * ``reduce`` and ``broadcast``, f32, p = 4 and p = 3, one 25 MiB
    gradient bucket per rank (the PyTorch DDP ``bucket_cap_mb`` default),
    n = 8;
  * ``quantized_allreduce``, f32, p = 4, one 4 MiB bucket per rank (the
    ``optim.compression`` bucket default);
  * ``allgather``, f32, p = 4, 4 MiB per rank.

The f32 payloads are integer-valued, so sums are exact: every result is
compared bit-exact with a plain NumPy reference and across the two
backends.  The quantized kind is compared with its NumPy replay
(:func:`repro.core.simulator.replay_quantized_allreduce`) within
``QUANT_TOL_STEPS`` quantization steps.

``--chips 4`` runs only the cross-chip phase: ``CirculantComm`` plans on
a 4-device mesh and on a 3-device sub-mesh, each kind against XLA's own
collective (``psum``, ``psum_scatter``, ``all_gather``), on the ``jnp``
backend and once more on ``pallas``, then ``broadcast_state`` of a
qwen2-0.5b-shaped bf16 state (about 1 GB per rank) from root 0.

Every phase prints kind, p, n, bytes per rank, backend, compile seconds
(the first call's wall time less the warm call's), the wall time of one
warm call ended by ``block_until_ready`` -- smoke timings, not a
benchmark -- and the max abs diff against its reference.  Any mismatch
or exception exits non-zero.  The last line of standard output is

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}

The script refuses to run off TPU.  JAX's persistent compile cache lives
in ``$JAX_COMPILATION_CACHE_DIR`` when that is set, and otherwise in
``.jax_cache/`` next to this file.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core.comm import CirculantComm, host_plan  # noqa: E402
from repro.core.roundstep import get_round_step  # noqa: E402
from repro.core.simulator import replay_quantized_allreduce  # noqa: E402

BUCKET_BYTES = 25 * 2**20       # PyTorch DDP bucket_cap_mb=25
QBUCKET_BYTES = 4 * 2**20       # optim.compression default bucket
N_BLOCKS = 8
BACKENDS = ("jnp", "pallas")
#: The quantized kind must match its NumPy replay, and its two backends
#: each other, to within this many quantization steps (one step = the
#: block's scale, amax / 127).  The exact kinds are held bit-exact.  On
#: a v5e chip both backends land up to one step from the replay: the
#: replay emulates a fused multiply-add for the dequantize-accumulate,
#: the chip rounds the product first, so partial sums differ in the last
#: bit, and one on a rounding boundary requantizes one step apart.
QUANT_TOL_STEPS = 1
#: Slack on the step count: the step is recomputed from the dequantized
#: reference block, so a one-step difference reads 1 +- 1e-6.
_STEP_SLACK = 1e-3


# ------------------------------------------------------------ set-up


def setup_compile_cache() -> str:
    """Point JAX's persistent compile cache at a fixed directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        REPO / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def _timed(fn):
    """(result, compile_s, warm_s): a cold call, then one warm call,
    each ended by block_until_ready."""
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    warm = time.perf_counter() - t0
    return out, max(0.0, cold - warm), warm


def _report(kind, p, n, nbytes, backend, compile_s, warm_s, diff,
            unit="abs"):
    print(f"[smoke] kind={kind} p={p} n={n} bytes_per_rank={nbytes} "
          f"backend={backend} compile_s={compile_s:.3f} "
          f"warm_call_s={warm_s:.4f} max_{unit}_diff={diff}", flush=True)


def _ints(rng, shape):
    """Integer-valued f32 payload: sums of a few ranks stay exact."""
    return rng.integers(-1024, 1025, size=shape, dtype=np.int32).astype(
        np.float32)


# ----------------------------------------------------- one-chip phase


def host_case(kind, p, bucket_bytes, seed, n=N_BLOCKS):
    """Run one host-plan collective on both backends and check it.

    Returns the per-backend records.  Raises AssertionError on any
    mismatch with the NumPy reference or between the backends.
    """
    elems = bucket_bytes // 4
    bs = elems // n
    root = p - 1 if kind in ("broadcast", "reduce") else 0
    rng = np.random.default_rng([seed, p, len(kind)])
    if kind == "broadcast":
        vals = _ints(rng, (n, bs))
    elif kind == "quantized_allreduce":
        # high dynamic range across quantization blocks
        vals = (rng.standard_normal((p, n, bs), np.float32)
                * np.float32(10.0) ** rng.integers(-3, 4, size=(p, n, 1))
                ).astype(np.float32)
    else:
        vals = _ints(rng, (p, n, bs))
    records, first, ref = [], None, None
    for backend in BACKENDS:
        plan = host_plan(kind, p, n, root=root, backend=backend)
        out, compile_s, warm_s = _timed(lambda: plan.run(vals))
        if kind in ("broadcast", "allgather"):
            diff = float(np.abs(out - vals[None]).max())
        elif kind == "reduce":
            diff = float(np.abs(out[root] - vals.sum(0, dtype=np.float64)
                                ).max())
        else:
            if ref is None:
                ref = replay_quantized_allreduce(plan, vals)
            diff, off, flips = _quant_steps(out[0], ref[0])
            _assert_complete(vals, *out)
            print(f"[smoke] kind={kind} p={p} backend={backend} "
                  f"elements_off_replay={off} of {out[0].size}, "
                  f"a_step_off={flips}", flush=True)
        tol = (QUANT_TOL_STEPS + _STEP_SLACK if kind == "quantized_allreduce"
               else 0)
        _report(kind, p, n, elems * 4, backend, compile_s, warm_s, diff,
                "step" if kind == "quantized_allreduce" else "abs")
        records.append(dict(kind=kind, p=p, n=n, backend=backend,
                            compile_s=compile_s, warm_s=warm_s, diff=diff))
        assert diff <= tol, f"{kind} p={p} {backend}: diff {diff} > {tol}"
        if first is None:
            first = out
        else:
            _assert_backends_agree(kind, first, out, f"p={p}")
    return records


def _quant_steps(out, ref, qb=256):
    """(max |out - ref| in quantization steps, elements that differ at
    all, elements a half step or more apart); a step is the reference
    block's amax / 127."""
    ref_b = ref.reshape(-1, qb).astype(np.float64)
    step = np.abs(ref_b).max(axis=1, keepdims=True) / 127.0
    diff = np.abs(out.reshape(-1, qb).astype(np.float64) - ref_b)
    steps = diff / np.where(step > 0, step, 1.0)
    return (float(steps.max()), int(np.count_nonzero(diff)),
            int(np.count_nonzero(steps >= 0.5)))


def _assert_complete(vals, out, err):
    """Error feedback is complete: lossy sum + all ranks' errors == the
    exact sum, up to f32 accumulation order."""
    exact = vals.astype(np.float64).sum(0)
    recon = out[0].astype(np.float64) + err.astype(np.float64).sum(0)
    tol = 1e-4 * np.maximum(np.abs(exact), np.abs(vals).max(0) * len(vals))
    assert (np.abs(recon - exact) <= tol + 1e-7).all(), "incomplete error"


def _assert_backends_agree(kind, a, b, what):
    """jnp and pallas results: bit-equal for the exact kinds; the lossy
    sums of the quantized kind within ``QUANT_TOL_STEPS`` steps."""
    if kind != "quantized_allreduce":
        return _assert_same(a, b, f"{what}: jnp vs pallas")
    steps, off, _ = _quant_steps(np.asarray(b[0]), np.asarray(a[0]))
    print(f"[smoke] kind={kind} {what} jnp_vs_pallas_step_diff={steps} "
          f"elements_off={off}", flush=True)
    assert steps <= QUANT_TOL_STEPS + _STEP_SLACK, (
        f"{what}: backends {steps} steps apart")


def _assert_same(a, b, what):
    """Bit equality of one array or a tuple of arrays."""
    a = a if isinstance(a, tuple) else (a,)
    b = b if isinstance(b, tuple) else (b,)
    for x, y in zip(a, b):
        x, y = np.asarray(x), np.asarray(y)
        assert x.shape == y.shape and x.tobytes() == y.tobytes(), what


def one_chip_phase(seed, bucket_bytes=BUCKET_BYTES,
                   qbucket_bytes=QBUCKET_BYTES):
    """The default phase: every host-plan kind at deployment sizes."""
    if jax.default_backend() == "tpu":
        assert get_round_step("pallas").interpret is False, (
            "the pallas round step runs interpreted on the chip")
    records = []
    for p in (4, 3):
        for kind in ("reduce", "broadcast"):
            records += host_case(kind, p, bucket_bytes, seed)
    records += host_case("quantized_allreduce", 4, qbucket_bytes, seed)
    records += host_case("allgather", 4, qbucket_bytes, seed)
    return records


# --------------------------------------------------- four-chip phase


def _sharded(mesh, fn, key, shape, dtype):
    """Generate [p, ...] data on the devices, one row per rank."""
    return jax.jit(lambda k: fn(k, shape).astype(dtype),
                   out_shardings=NamedSharding(mesh, P("x")))(key)


def _xla(mesh, fn, out_spec=P("x")):
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P("x"),
                                 out_specs=out_spec, check_vma=False))


def _max_diff(a, b) -> float:
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def _spans(mesh, *arrays):
    want = set(mesh.devices.flat)
    for a in arrays:
        assert a.sharding.device_set == want, (
            f"output on {a.sharding.device_set}, mesh is {want}")


def mesh_cases(mesh, backend, seed, bucket_bytes=BUCKET_BYTES,
               qbucket_bytes=QBUCKET_BYTES, n=N_BLOCKS):
    """Each CirculantComm kind on ``mesh`` against XLA's collective.
    Returns ``(records, outputs)`` with the outputs keyed by kind."""
    p = mesh.shape["x"]
    comm = CirculantComm(mesh, "x", backend=backend)
    key = jax.random.key(seed * 1000 + p)
    ints = lambda k, s: jax.random.randint(k, s, -1024, 1025)  # noqa: E731
    elems = bucket_bytes // 4
    records, outputs = [], {}

    def case(kind, x, ref):
        plan = comm.plan(kind, x, n_blocks=n)
        out, compile_s, warm_s = _timed(lambda: plan(x))
        _spans(mesh, *jax.tree.leaves(out))
        if kind == "quantized_allreduce":
            diff = _check_quantized(mesh, x, out, ref)
        else:
            diff = _max_diff(out, ref)
        per_rank = x.size // p * x.dtype.itemsize
        _report(kind, p, n, per_rank, backend, compile_s, warm_s, diff,
                "step" if kind == "quantized_allreduce" else "abs")
        records.append(dict(kind=kind, p=p, n=n, backend=backend,
                            compile_s=compile_s, warm_s=warm_s, diff=diff))
        assert kind == "quantized_allreduce" or diff == 0, (
            f"{kind} p={p} {backend}: max abs diff {diff}")
        outputs[kind] = jax.device_get(out)

    x = _sharded(mesh, ints, key, (p, elems), jnp.float32)
    case("allreduce", x, _xla(mesh, lambda a: jax.lax.psum(a, "x"))(x))
    L = elems - elems % p
    x = _sharded(mesh, ints, jax.random.fold_in(key, 1), (p, L), jnp.float32)
    case("reduce_scatter", x, _xla(mesh, lambda a: jax.lax.psum_scatter(
        a, "x", scatter_dimension=1, tiled=True))(x))
    m = qbucket_bytes // 4
    x = _sharded(mesh, ints, jax.random.fold_in(key, 2), (p, m), jnp.float32)
    case("allgather", x, _xla(mesh, lambda a: jax.lax.all_gather(
        a, "x", tiled=True), P())(x))
    x = _sharded(mesh, jax.random.normal, jax.random.fold_in(key, 3),
                 (p, m), jnp.float32)
    case("quantized_allreduce", x,
         _xla(mesh, lambda a: jax.lax.psum(a, "x"))(x))
    return records, outputs


def _check_quantized(mesh, x, out, exact):
    """The int8-wire allreduce against psum: its error feedback is
    complete (lossy sum + psum of the errors == psum, to f32 order) and
    the lossy sum is within one quantization step per rank of psum.
    Returns the max deviation from psum in quantization steps."""
    p = mesh.shape["x"]
    sums, errs = out
    recon = sums + _xla(mesh, lambda a: jax.lax.psum(a, "x"))(errs)
    scale = jnp.abs(x).max() * p / 127.0        # coarsest possible step
    assert _max_diff(recon, exact) <= 1e-4 * float(jnp.abs(exact).max()
                                                   + scale * 127.0), (
        "quantized allreduce: error feedback incomplete")
    steps = _max_diff(sums, exact) / float(scale)
    assert steps <= p, f"quantized allreduce off by {steps} steps"
    return steps


def broadcast_state_case(mesh, seed, arch="qwen2-0.5b", smoke=False):
    """Restore fan-out of a model-shaped bf16 state from root 0."""
    from repro.configs import get_config
    from repro.models.transformer import init_params
    from repro.train.restore_broadcast import broadcast_state, restore_plan

    p = mesh.shape["x"]
    cfg = get_config(arch, smoke=smoke)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.key(0))
    leaves, treedef = jax.tree.flatten(shapes)
    key = jax.random.key(seed)
    state = jax.tree.unflatten(treedef, [
        _sharded(mesh, jax.random.normal, jax.random.fold_in(key, i),
                 (p,) + leaf.shape, jnp.bfloat16)
        for i, leaf in enumerate(leaves)])
    out, compile_s, warm_s = _timed(
        lambda: broadcast_state(mesh, "x", state, root=0))
    _spans(mesh, *jax.tree.leaves(out))
    bits = lambda a: jax.lax.bitcast_convert_type(a, jnp.uint16)  # noqa
    bad = sum(int(jnp.sum(bits(o) != bits(s[:1])))
              for o, s in zip(jax.tree.leaves(out), jax.tree.leaves(state)))
    per_rank = sum(leaf.size for leaf in leaves) * 2
    n = restore_plan(p, per_rank)[1]           # the block count it chose
    _report("broadcast_state", p, n, per_rank, "jnp", compile_s, warm_s,
            bad, "mismatched_elems")
    assert bad == 0, f"broadcast_state: {bad} elements differ from root"
    return [dict(kind="broadcast_state", p=p, n=n, backend="jnp",
                 compile_s=compile_s, warm_s=warm_s, diff=bad)]


def four_chip_phase(devices, seed, bucket_bytes=BUCKET_BYTES,
                    qbucket_bytes=QBUCKET_BYTES, arch_smoke=False):
    """The ``--chips 4`` phase: plans across chips, against XLA."""
    assert len(devices) >= 4, f"needs 4 devices, found {len(devices)}"
    records = []
    for p in (4, 3):
        mesh = Mesh(np.array(devices[:p]), ("x",))
        first = None
        for backend in BACKENDS:
            recs, outs = mesh_cases(mesh, backend, seed, bucket_bytes,
                                    qbucket_bytes)
            records += recs
            if first is None:
                first = outs
                continue
            for kind, out in outs.items():
                _assert_backends_agree(kind, first[kind], out, f"p={p}")
    mesh = Mesh(np.array(devices[:4]), ("x",))
    records += broadcast_state_case(mesh, seed, smoke=arch_smoke)
    return records


# ---------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[1])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    cache = setup_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    print(f"[smoke] device={dev.device_kind} count={len(devices)} "
          f"compile_cache={cache}; timings are smoke timings, not a "
          f"benchmark", flush=True)
    if args.chips == 4:
        four_chip_phase(devices, args.seed)
    else:
        one_chip_phase(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
