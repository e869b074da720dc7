"""Multi-device collective worker: run under XLA host-device flags.

Invoked as a subprocess by test_collectives.py (and by the collective
benchmarks) so that the main process keeps its single-device view:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tests/mp_worker.py <what> <p>
"""

import os
import sys

if __name__ == "__main__":
    what = sys.argv[1] if len(sys.argv) > 1 else "all"
    p = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    backend = sys.argv[3] if len(sys.argv) > 3 else "jnp"
    # "hier" mode: argv[4] is the node count of the nodes x cores mesh
    # (cores = p // nodes).
    nodes = int(sys.argv[4]) if len(sys.argv) > 4 else 2
    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={p}"
    )

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.collectives import (
    circulant_allbroadcast,
    circulant_allgather,
    circulant_allgatherv,
    circulant_allreduce,
    circulant_broadcast,
    circulant_reduce,
    ring_allgather,
)


def make_mesh(p):
    return Mesh(np.array(jax.devices()[:p]), ("data",))


def sharded(mesh, arr):
    return jax.device_put(arr, NamedSharding(mesh, P("data")))


def check_broadcast(p, n_blocks, root, elems=97, dtype=jnp.float32,
                    backend="jnp"):
    mesh = make_mesh(p)
    rng = np.random.default_rng(0)
    data = rng.normal(size=(p, elems)).astype(dtype)
    x = sharded(mesh, jnp.asarray(data))
    out = jax.jit(
        lambda a: circulant_broadcast(mesh, "data", a, n_blocks=n_blocks,
                                      root=root, backend=backend)
    )(x)
    out = np.asarray(out)
    for r in range(p):
        np.testing.assert_allclose(out[r], data[root], rtol=0, atol=0)
    print(f"broadcast p={p} n={n_blocks} root={root} backend={backend} ok")


def check_allgather(p, n_blocks, elems=64, dtype=jnp.float32, backend="jnp"):
    mesh = make_mesh(p)
    rng = np.random.default_rng(1)
    data = rng.normal(size=(p * elems,)).astype(dtype)
    x = sharded(mesh, jnp.asarray(data))
    out = jax.jit(
        lambda a: circulant_allgather(mesh, "data", a, n_blocks=n_blocks,
                                      backend=backend)
    )(x)
    np.testing.assert_allclose(np.asarray(out), data, rtol=0, atol=0)
    print(f"allgather p={p} n={n_blocks} backend={backend} ok")


def check_allgatherv(p, n_blocks, sizes, dtype=jnp.int32, backend="jnp"):
    mesh = make_mesh(p)
    cap = max(max(sizes), 1)
    rng = np.random.default_rng(2)
    rows = np.zeros((p, cap), dtype=np.int32)
    for j in range(p):
        rows[j, : sizes[j]] = rng.integers(0, 1000, size=sizes[j])
    x = sharded(mesh, jnp.asarray(rows))
    out = jax.jit(
        lambda a: circulant_allgatherv(mesh, "data", a, sizes,
                                       n_blocks=n_blocks, backend=backend)
    )(x)
    out = np.asarray(out)
    for j in range(p):
        np.testing.assert_array_equal(out[j, : sizes[j]], rows[j, : sizes[j]])
    print(f"allgatherv p={p} n={n_blocks} sizes={sizes} backend={backend} ok")


def check_compressed_allreduce(p, elems=2048, backend="jnp"):
    """Both lossy transports (legacy ring, quantized circulant): mean
    contract, COMPLETE error feedback vs the exact f32 psum on
    adversarial high-dynamic-range gradients, ragged leaf sizes,
    bf16 leaves, and nonfinite propagation."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.optim.compression import (
        BLOCK,
        compressed_allreduce_tree,
        init_error_state,
    )

    mesh = make_mesh(p)
    rng = np.random.default_rng(7)
    # adversarial dynamic range: per-block magnitudes spanning 12 decades
    # (a uniform-scale gradient hides the per-hop error bug -- partial
    # sums then quantize with ~the same scale as the inputs).
    nblk = max(1, elems // BLOCK)
    mags = 10.0 ** rng.integers(-6, 6, size=(p, nblk, 1))
    data = (rng.normal(size=(p, nblk, BLOCK)) * mags).astype(
        np.float32).reshape(p, -1)
    elems = data.shape[1]
    # ragged second leaf: not divisible by p*BLOCK (padded-tail error
    # accounting), bf16 third leaf (f32 error state + downcast delta).
    rag = rng.normal(size=(p, 3 * BLOCK + 17)).astype(np.float32) * 100.0
    bfl = rng.normal(size=(p, 37)).astype(np.float32)

    for transport in ("ring", "circulant"):
        def body(xs, ys, zs):
            g = {"w": xs[0], "r": ys[0], "t": zs[0].astype(jnp.bfloat16)}
            e = init_error_state(g)
            red, new_e = compressed_allreduce_tree(
                g, e, "data", p, transport=transport, backend=backend)
            tot = jax.tree.map(lambda v: jax.lax.psum(v, "data"), new_e)
            red = jax.tree.map(lambda v: v.astype(jnp.float32), red)
            return (jax.tree.map(lambda v: v[None], red),
                    jax.tree.map(lambda v: v[None], tot))

        red, tot = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P("data"),) * 3,
            out_specs=({k: P("data") for k in "wrt"},) * 2,
            check_vma=False,
        ))(sharded(mesh, jnp.asarray(data)),
           sharded(mesh, jnp.asarray(rag)),
           sharded(mesh, jnp.asarray(bfl)))
        srcs = {"w": data, "r": rag,
                "t": np.asarray(jnp.asarray(bfl).astype(jnp.bfloat16),
                                np.float32)}
        for k, src in srcs.items():
            exact_sum = src.astype(np.float64).sum(0)
            got = np.asarray(red[k], np.float64)
            te = np.asarray(tot[k], np.float64)
            # mean contract (loose sanity: one-shot lossy error is set by
            # the quantization-block amax, ~amax*p/127 per element; the
            # tight per-element claim is the completeness check below)
            lim = np.float64(5.0) * p * np.abs(src).max() / 127.0 + 1e-6
            assert (np.abs(got - exact_sum[None] / p) < lim).all()
            # completeness: exact_sum == p*mean + psum(err), to f32
            # accumulation tolerance -- this is what the old ring failed
            # by a factor of p plus every dropped per-hop error.
            for r in range(p):
                resid = np.abs(got[r] * p + te[r] - exact_sum)
                tol = 1e-4 * np.maximum(np.abs(exact_sum),
                                        np.abs(src).max(0) * p) + 1e-6
                assert (resid <= tol).all(), (
                    f"{transport}/{k} r={r}: error feedback incomplete, "
                    f"max resid {resid.max():.3e}")
        print(f"compressed_allreduce p={p} transport={transport} "
              f"backend={backend} ok")

    # nonfinite: a NaN lane poisons exactly its own quantization block
    # in the result (deterministic all-NaN), never the error state.
    bad = data.copy()
    bad[0, BLOCK + 3] = np.nan

    def nf_body(xs):
        g = {"w": xs[0]}
        e = init_error_state(g)
        red, new_e = compressed_allreduce_tree(g, e, "data", p,
                                               backend=backend)
        return red["w"][None], new_e["w"][None]

    red, err = jax.jit(shard_map(
        nf_body, mesh=mesh, in_specs=P("data"),
        out_specs=(P("data"), P("data")), check_vma=False,
    ))(sharded(mesh, jnp.asarray(bad)))
    red, err = np.asarray(red), np.asarray(err)
    for r in range(p):
        assert np.isnan(red[r, BLOCK:2 * BLOCK]).all(), \
            "NaN block not propagated"
        assert np.isfinite(red[r, 2 * BLOCK:]).all()
        assert np.isfinite(red[r, :BLOCK]).all()
    assert np.isfinite(err).all(), "error state poisoned by NaN input"
    print(f"compressed_allreduce p={p} nonfinite backend={backend} ok")


def check_gradsync(p, backend="jnp", steps=20):
    """End-to-end trainer parity: grad_sync='compressed' tracks
    grad_sync='auto' loss within bounded divergence over ``steps``
    optimizer steps (same data, same init)."""
    from repro.configs import get_config
    from repro.optim.adamw import AdamWConfig
    from repro.train.trainer import (
        TrainConfig,
        init_train_state,
        make_train_step,
    )

    mesh = make_mesh(p)
    cfg = get_config("qwen2-0.5b", smoke=True)
    B, S = 2 * p, 32
    rng = np.random.default_rng(41)
    toks = rng.integers(0, cfg.vocab, size=(steps, B, S))

    def run(grad_sync):
        tcfg = TrainConfig(
            microbatches=2, remat="none",
            opt=AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=steps),
            dp_axes=("data",), grad_sync=grad_sync,
            grad_sync_backend=backend,
        )
        state = init_train_state(cfg, tcfg, jax.random.PRNGKey(0),
                                 mesh=mesh)
        step = jax.jit(make_train_step(cfg, tcfg, mesh=mesh))
        losses = []
        with mesh:
            for i in range(steps):
                tok = sharded(mesh, jnp.asarray(toks[i]))
                state, m = step(state, {"tokens": tok, "labels": tok})
                losses.append(float(m["loss"]))
        return np.array(losses)

    auto = run("auto")
    comp = run("compressed")
    # both must actually train...
    assert auto[-1] < auto[0] and comp[-1] < comp[0], (auto, comp)
    # ...and stay within bounded divergence: int8 + error feedback is a
    # tiny perturbation at these scales.
    div = np.abs(auto - comp)
    assert div.max() < 0.05 * max(1.0, auto[0]), \
        f"loss trajectories diverged: {div.max():.4f}\nauto={auto}\ncomp={comp}"
    print(f"gradsync parity p={p} backend={backend} ok "
          f"(max |auto-comp| {div.max():.4g} over {steps} steps)")


def check_overlap(p, backend="jnp"):
    """Overlapped (double-buffered) executor vs sequential on a live
    mesh: distinct cached plans, bit-equal outputs for every kind that
    gains the mode (mixed-dtype pytrees, nonzero roots, max reduces)."""
    from repro.core.comm import get_comm

    mesh = make_mesh(p)
    comm = get_comm(mesh, "data", backend=backend)
    rng = np.random.default_rng(43)
    # "t" holds 3 blocks of 8192 integer-valued floats: the jnp round
    # step lays them out as tile stacks, and every sum is exact.
    t = rng.integers(-9, 9, size=(p, 3 * 8192)).astype(np.float32)
    xs = {"w": sharded(mesh, jnp.asarray(
        rng.normal(size=(p, 37)).astype(np.float32))),
        "b": sharded(mesh, jnp.asarray(
            rng.integers(-9, 9, size=(p, 11)).astype(np.int32))),
        "t": sharded(mesh, jnp.asarray(t))}
    want_t = {"broadcast": np.broadcast_to(t[p - 1], t.shape),
              "allgather": t,
              "reduce": np.where(np.arange(p)[:, None] == p - 1,
                                 t.sum(0), 0),
              "allreduce": np.broadcast_to(t.sum(0), t.shape)}
    for kind in ("broadcast", "allgather", "reduce", "allreduce"):
        rooted = kind in ("broadcast", "reduce")
        kw = dict(n_blocks=3, root=p - 1 if rooted else 0)
        seq = comm.plan(kind, xs, **kw)
        ovl = comm.plan(kind, xs, overlap=True, **kw)
        assert ovl is not seq and ovl.overlap and not seq.overlap, \
            f"{kind}: overlap plan not distinct from sequential"
        assert seq.tiled_leaves == (1 if backend == "jnp" else 3), \
            seq.describe()
        a, b = seq(xs), ovl(xs)
        for k in ("w", "b", "t"):
            np.testing.assert_array_equal(np.asarray(a[k]),
                                          np.asarray(b[k]))
        np.testing.assert_array_equal(np.asarray(a["t"]), want_t[kind])
        print(f"overlap {kind} p={p} backend={backend} ok")
    # max-op reduce: the staged drain path must match for non-sum ops.
    fs = {"a": xs["w"]}
    a = comm.reduce(fs, n_blocks=2, root=0, op="max")
    b = comm.reduce(fs, n_blocks=2, root=0, op="max", overlap=True)
    np.testing.assert_array_equal(np.asarray(a["a"]), np.asarray(b["a"]))
    print(f"overlap reduce(max) p={p} backend={backend} ok")
    # reduce_scatter needs p-divisible shards.
    m = {"m": sharded(mesh, jnp.asarray(
        rng.normal(size=(p, p * 8)).astype(np.float32))),
        "t": sharded(mesh, jnp.asarray(t[:, :p * 2048]))}  # tiled shards
    a = comm.reduce_scatter(m, n_blocks=2)
    b = comm.reduce_scatter(m, n_blocks=2, overlap=True)
    for k in ("m", "t"):
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
    np.testing.assert_array_equal(
        np.asarray(a["t"]), t[:, :p * 2048].sum(0).reshape(p, 2048))
    print(f"overlap reduce_scatter p={p} backend={backend} ok")
    # unsupported kinds must be rejected, not silently sequential.
    try:
        comm.plan("quantized_allreduce", {"g": sharded(mesh, jnp.asarray(
            rng.normal(size=(p, 512)).astype(np.float32)))},
            qblock=256, overlap=True)
    except ValueError:
        pass
    else:
        raise AssertionError("quantized_allreduce accepted overlap=True")


def check_gradsync_stream(p, backend="jnp", steps=12):
    """Streamed (in-backward, bucket-at-a-time) vs post-backward
    compressed grad sync: loss trajectories stay within bounded
    divergence over ``steps`` optimizer steps (same data, same init),
    with and without gradient accumulation."""
    from repro.configs import get_config
    from repro.optim.adamw import AdamWConfig
    from repro.train.trainer import (
        TrainConfig,
        init_train_state,
        make_train_step,
    )

    mesh = make_mesh(p)
    cfg = get_config("qwen2-0.5b", smoke=True)
    B, S = 2 * p, 32
    rng = np.random.default_rng(47)
    toks = rng.integers(0, cfg.vocab, size=(steps, B, S))

    def run(stream, microbatches):
        tcfg = TrainConfig(
            microbatches=microbatches, remat="none",
            opt=AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=steps),
            dp_axes=("data",), grad_sync="compressed",
            grad_sync_backend=backend, stream_grad_sync=stream,
        )
        state = init_train_state(cfg, tcfg, jax.random.PRNGKey(0),
                                 mesh=mesh)
        step = jax.jit(make_train_step(cfg, tcfg, mesh=mesh))
        losses = []
        with mesh:
            for i in range(steps):
                tok = sharded(mesh, jnp.asarray(toks[i]))
                state, m = step(state, {"tokens": tok, "labels": tok})
                losses.append(float(m["loss"]))
        return np.array(losses)

    for mb in (1, 2):
        base = run(False, mb)
        strm = run(True, mb)
        assert base[-1] < base[0] and strm[-1] < strm[0], (base, strm)
        div = np.abs(base - strm)
        assert div.max() < 0.05 * max(1.0, base[0]), (
            f"streamed sync diverged (microbatches={mb}): {div.max():.4f}"
            f"\nbase={base}\nstrm={strm}")
        print(f"gradsync stream parity p={p} microbatches={mb} "
              f"backend={backend} ok (max div {div.max():.4g})")


def check_reduce_scatter(p):
    from repro.core.collectives import circulant_reduce_scatter

    mesh = make_mesh(p)
    rng = np.random.default_rng(13)
    for n in (1, 2, 3, 6):
        L = p * 24
        data = rng.normal(size=(p, L)).astype(np.float32)
        x = sharded(mesh, jnp.asarray(data))
        out = jax.jit(
            lambda a: circulant_reduce_scatter(mesh, "data", a, n_blocks=n)
        )(x)
        out = np.asarray(out)
        expect = data.sum(axis=0).reshape(p, -1)
        np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-4)
        print(f"reduce_scatter p={p} n={n} ok")


def check_restore_broadcast(p):
    """Restore fan-out: root rank's checkpoint pytree reaches every rank."""
    from jax.sharding import PartitionSpec as P
    from repro.train.restore_broadcast import broadcast_state

    mesh = make_mesh(p)
    rng = np.random.default_rng(11)
    w = rng.normal(size=(p, 33, 7)).astype(np.float32)   # only row 0 is "real"
    b = rng.normal(size=(p, 13)).astype(np.float32)
    state = {
        "w": sharded(mesh, jnp.asarray(w)),
        "b": sharded(mesh, jnp.asarray(b)),
    }
    out = jax.jit(lambda s: broadcast_state(mesh, "data", s, n_blocks=3))(state)
    for r in range(p):
        np.testing.assert_allclose(np.asarray(out["w"])[r], w[0], atol=0)
        np.testing.assert_allclose(np.asarray(out["b"])[r], b[0], atol=0)
    print(f"restore_broadcast p={p} ok")


def check_reduce(p, backend="jnp"):
    """Reversed-schedule reduction: root slice = op-reduction, rest zero."""
    mesh = make_mesh(p)
    rng = np.random.default_rng(17)
    for n in (1, 2, 3, 5):
        for root in sorted({0, p - 1}):
            data = rng.integers(-1000, 1000, size=(p, 41)).astype(np.int32)
            x = sharded(mesh, jnp.asarray(data))
            out = np.asarray(jax.jit(
                lambda a: circulant_reduce(mesh, "data", a, n_blocks=n,
                                           root=root, backend=backend)
            )(x))
            np.testing.assert_array_equal(out[root], data.sum(axis=0))
            for r in range(p):
                if r != root:
                    assert not out[r].any(), f"non-root rank {r} not zeroed"
            fdata = rng.normal(size=(p, 41)).astype(np.float32)
            xf = sharded(mesh, jnp.asarray(fdata))
            outf = np.asarray(jax.jit(
                lambda a: circulant_reduce(
                    mesh, "data", a, n_blocks=n, root=root, op="max",
                    backend=backend)
            )(xf))
            np.testing.assert_array_equal(outf[root], fdata.max(axis=0))
            print(f"reduce p={p} n={n} root={root} backend={backend} ok")


def check_allreduce(p, backend="jnp"):
    """Composed reduce+broadcast: every rank holds the full reduction."""
    mesh = make_mesh(p)
    rng = np.random.default_rng(19)
    for n in (1, 2, 4):
        data = rng.integers(-1000, 1000, size=(p, 53)).astype(np.int32)
        x = sharded(mesh, jnp.asarray(data))
        out = np.asarray(jax.jit(
            lambda a: circulant_allreduce(mesh, "data", a, n_blocks=n,
                                          backend=backend)
        )(x))
        expect = data.sum(axis=0)
        for r in range(p):
            np.testing.assert_array_equal(out[r], expect)
        fdata = rng.normal(size=(p, 53)).astype(np.float32)
        xf = sharded(mesh, jnp.asarray(fdata))
        outf = np.asarray(jax.jit(
            lambda a: circulant_allreduce(mesh, "data", a, n_blocks=n,
                                          op="max", backend=backend)
        )(xf))
        expectf = fdata.max(axis=0)
        for r in range(p):
            np.testing.assert_array_equal(outf[r], expectf)
        print(f"allreduce p={p} n={n} backend={backend} ok")


def check_allbroadcast(p, elems=48):
    mesh = make_mesh(p)
    rng = np.random.default_rng(23)
    for n in (1, 3):
        data = rng.normal(size=(p * elems,)).astype(np.float32)
        x = sharded(mesh, jnp.asarray(data))
        out = np.asarray(jax.jit(
            lambda a: circulant_allbroadcast(mesh, "data", a, n_blocks=n)
        )(x))
        np.testing.assert_allclose(out, data, rtol=0, atol=0)
        print(f"allbroadcast p={p} n={n} ok")


def check_comm(p, backend="jnp"):
    """Plan/execute communicator with pytree payloads: dict/tuple trees,
    mixed dtypes, ragged leaves (sizes not divisible by n_blocks), both
    data-plane backends -- certified bit-exact against per-leaf NumPy
    references, with plan-cache identity asserted along the way."""
    from repro.core.comm import get_comm, payload_spec

    mesh = make_mesh(p)
    comm = get_comm(mesh, "data", backend=backend)
    rng = np.random.default_rng(29)

    # ---- broadcast: dict-of-(arrays + tuple) payload, mixed dtypes,
    # ragged leaf sizes (111, 11, 5 elems with n=4 blocks), nonzero root.
    root = p - 1
    state = {
        "w": rng.normal(size=(p, 37, 3)).astype(np.float32),
        "b": rng.integers(0, 100, size=(p, 11)).astype(np.int32),
        "t": (rng.normal(size=(p, 5)).astype(jnp.bfloat16),),
    }
    xs = {"w": sharded(mesh, jnp.asarray(state["w"])),
          "b": sharded(mesh, jnp.asarray(state["b"])),
          "t": (sharded(mesh, jnp.asarray(state["t"][0])),)}
    plan = comm.plan("broadcast", xs, n_blocks=4, root=root)
    assert plan is comm.plan("broadcast", payload_spec(xs), n_blocks=4,
                             root=root), "plan cache lost identity"
    out = plan(xs)
    for k in ("w", "b"):
        np.testing.assert_array_equal(
            np.asarray(out[k]), np.broadcast_to(state[k][root], state[k].shape))
    np.testing.assert_array_equal(
        np.asarray(out["t"][0], np.float32),
        np.broadcast_to(np.asarray(state["t"][0], np.float32)[root],
                        state["t"][0].shape))
    out2 = plan(xs)  # second execution reuses the compiled rounds
    np.testing.assert_array_equal(np.asarray(out2["b"]), np.asarray(out["b"]))
    print(f"comm broadcast pytree p={p} root={root} backend={backend} ok")

    # ---- reduce: int sum is bit-exact; non-root slices zeroed.
    data = {"a": rng.integers(-50, 50, size=(p, 13)).astype(np.int32),
            "b": rng.integers(-50, 50, size=(p, 7, 2)).astype(np.int32)}
    ds = {k: sharded(mesh, jnp.asarray(v)) for k, v in data.items()}
    red = comm.reduce(ds, n_blocks=3, root=1)
    np.testing.assert_array_equal(np.asarray(red["a"])[1], data["a"].sum(0))
    np.testing.assert_array_equal(np.asarray(red["b"])[1], data["b"].sum(0))
    for r in range(p):
        if r != 1:
            assert not np.asarray(red["a"])[r].any()
    # float max is bit-exact too
    fdata = {"a": rng.normal(size=(p, 13)).astype(np.float32),
             "b": rng.normal(size=(p, 7, 2)).astype(np.float32)}
    fs = {k: sharded(mesh, jnp.asarray(v)) for k, v in fdata.items()}
    fred = comm.reduce(fs, n_blocks=3, root=0, op="max")
    np.testing.assert_array_equal(np.asarray(fred["a"])[0], fdata["a"].max(0))
    print(f"comm reduce pytree p={p} backend={backend} ok")

    # ---- allreduce: every rank ends with the per-leaf reduction.
    ar = comm.allreduce(ds, n_blocks=2)
    for r in range(p):
        np.testing.assert_array_equal(np.asarray(ar["a"])[r], data["a"].sum(0))
        np.testing.assert_array_equal(np.asarray(ar["b"])[r], data["b"].sum(0))
    print(f"comm allreduce pytree p={p} backend={backend} ok")

    # ---- allgather: replicated per-leaf, ragged shard sizes.
    g = {"x": rng.normal(size=(p * 6,)).astype(np.float32),
         "y": rng.integers(0, 9, size=(p, 4)).astype(np.int32)}
    gs = {k: sharded(mesh, jnp.asarray(v)) for k, v in g.items()}
    got = comm.allgather(gs, n_blocks=3)
    np.testing.assert_array_equal(np.asarray(got["x"]), g["x"])
    np.testing.assert_array_equal(np.asarray(got["y"]), g["y"])
    print(f"comm allgather pytree p={p} backend={backend} ok")

    # ---- reduce_scatter: summed shards, scattered rows.  The int case
    # uses magnitudes beyond float32's 24-bit mantissa, so it fails if
    # partials ever detour through float32 -- integer sums accumulate
    # natively and must be bit-exact.
    m = rng.normal(size=(p, p * 8)).astype(np.float32)
    rs = comm.reduce_scatter({"m": sharded(mesh, jnp.asarray(m))}, n_blocks=2)
    np.testing.assert_allclose(np.asarray(rs["m"]), m.sum(0).reshape(p, 8),
                               rtol=1e-5, atol=1e-4)
    mi = (rng.integers(-1000, 1000, size=(p, p * 8)) * 100003).astype(np.int32)
    rsi = comm.reduce_scatter({"m": sharded(mesh, jnp.asarray(mi))},
                              n_blocks=3)
    np.testing.assert_array_equal(np.asarray(rsi["m"]),
                                  mi.sum(0).reshape(p, 8))
    print(f"comm reduce_scatter pytree p={p} backend={backend} ok")

    # ---- plan keys normalize onto the resolved block count: auto
    # (n_blocks=None) and the explicit optimum share one plan/executor.
    auto_plan = comm.plan("broadcast", xs, root=root)
    assert comm.plan("broadcast", xs, n_blocks=auto_plan.n_blocks,
                     root=root) is auto_plan, "n_blocks key not normalized"

    # ---- allgatherv: per-leaf sizes pytree + one shared sizes list.
    sizes = {"u": [3 * j + 1 for j in range(p)], "v": [7] * p}
    vin = {"u": np.zeros((p, 3 * p), np.int32),
           "v": np.zeros((p, 9), np.float32)}
    for j in range(p):
        vin["u"][j, : sizes["u"][j]] = rng.integers(1, 99, size=sizes["u"][j])
        vin["v"][j, :7] = rng.normal(size=7)
    gv = comm.allgatherv({k: sharded(mesh, jnp.asarray(v))
                          for k, v in vin.items()}, sizes, n_blocks=2)
    for j in range(p):
        np.testing.assert_array_equal(np.asarray(gv["u"])[j, : sizes["u"][j]],
                                      vin["u"][j, : sizes["u"][j]])
        np.testing.assert_array_equal(np.asarray(gv["v"])[j, :7],
                                      vin["v"][j, :7])
    shared = comm.allgatherv({"v": sharded(mesh, jnp.asarray(vin["v"]))},
                             [7] * p, n_blocks=2)
    np.testing.assert_array_equal(np.asarray(shared["v"])[:, :7],
                                  vin["v"][:, :7])
    print(f"comm allgatherv pytree p={p} backend={backend} ok")

    # ---- shim equivalence: circulant_* resolves to the same plan cache.
    arr = sharded(mesh, jnp.asarray(state["w"]))
    a = circulant_broadcast(mesh, "data", arr, n_blocks=4, root=root,
                            backend=backend)
    b = comm.broadcast(arr, n_blocks=4, root=root)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print(f"comm shim equivalence p={p} backend={backend} ok")


def check_hier(nodes, cores, backend="jnp"):
    """Two-level hierarchical collectives on a (nodes x cores) mesh:
    dict/mixed-dtype pytree payloads for broadcast / reduce / allreduce
    / allgather, certified against per-leaf NumPy references, with
    plan-cache identity and the composed round counts asserted."""
    from jax.sharding import NamedSharding
    from repro.core.hier import get_hier_comm, hier_rounds

    p = nodes * cores
    mesh = Mesh(np.array(jax.devices()[:p]).reshape(nodes, cores),
                ("node", "core"))
    spec2d = NamedSharding(mesh, P(("node", "core")))
    hc = get_hier_comm(mesh, "node", "core", backend=backend)
    rng = np.random.default_rng(31)

    # ---- broadcast: dict pytree, mixed dtypes, ragged leaves, flat
    # root in the last node's last core.
    root = p - 1
    state = {
        "w": rng.normal(size=(p, 17, 3)).astype(np.float32),
        "b": rng.integers(0, 100, size=(p, 11)).astype(np.int32),
        "t": (rng.normal(size=(p, 5)).astype(jnp.bfloat16),),
    }
    xs = jax.tree.map(lambda a: jax.device_put(jnp.asarray(a), spec2d), state)
    plan = hc.plan("broadcast", xs, n_inter=2, n_intra=3, root=root)
    assert plan is hc.plan("broadcast", xs, n_inter=2, n_intra=3, root=root), \
        "hier plan cache lost identity"
    assert plan.rounds == hier_rounds("broadcast", nodes, cores, 2, 3)
    out = plan(xs)
    for k in ("w", "b"):
        np.testing.assert_array_equal(
            np.asarray(out[k]), np.broadcast_to(state[k][root],
                                                state[k].shape))
    np.testing.assert_array_equal(
        np.asarray(out["t"][0], np.float32),
        np.broadcast_to(np.asarray(state["t"][0], np.float32)[root],
                        state["t"][0].shape))
    print(f"hier broadcast {nodes}x{cores} root={root} backend={backend} ok")

    # ---- reduce: bit-exact int sum at the root, zeros elsewhere; float
    # max bit-exact too.
    data = {"a": rng.integers(-50, 50, size=(p, 13)).astype(np.int32),
            "b": rng.integers(-50, 50, size=(p, 7, 2)).astype(np.int32)}
    ds = jax.tree.map(lambda a: jax.device_put(jnp.asarray(a), spec2d), data)
    rroot = p // 2
    red = hc.reduce(ds, n_inter=1, n_intra=2, root=rroot)
    np.testing.assert_array_equal(np.asarray(red["a"])[rroot],
                                  data["a"].sum(0))
    np.testing.assert_array_equal(np.asarray(red["b"])[rroot],
                                  data["b"].sum(0))
    for r in range(p):
        if r != rroot:
            assert not np.asarray(red["a"])[r].any(), f"rank {r} not zeroed"
    fdata = {"a": rng.normal(size=(p, 13)).astype(np.float32)}
    fs = jax.tree.map(lambda a: jax.device_put(jnp.asarray(a), spec2d), fdata)
    fred = hc.reduce(fs, n_inter=2, n_intra=2, root=0, op="max")
    np.testing.assert_array_equal(np.asarray(fred["a"])[0],
                                  fdata["a"].max(0))
    print(f"hier reduce {nodes}x{cores} backend={backend} ok")

    # ---- allreduce: every rank ends with the per-leaf reduction.
    ar = hc.allreduce(ds, n_inter=2, n_intra=1)
    for r in range(p):
        np.testing.assert_array_equal(np.asarray(ar["a"])[r],
                                      data["a"].sum(0))
        np.testing.assert_array_equal(np.asarray(ar["b"])[r],
                                      data["b"].sum(0))
    arp = hc.plan("allreduce", ds, n_inter=2, n_intra=1)
    assert arp.rounds == hier_rounds("allreduce", nodes, cores, 2, 1)
    print(f"hier allreduce {nodes}x{cores} backend={backend} ok")

    # ---- allgather: replicated rank-major result, mixed dtypes.
    g = {"x": rng.normal(size=(p * 6,)).astype(np.float32),
         "y": rng.integers(0, 9, size=(p, 4)).astype(np.int32)}
    gs = jax.tree.map(lambda a: jax.device_put(jnp.asarray(a), spec2d), g)
    got = hc.allgather(gs, n_inter=2, n_intra=2)
    np.testing.assert_array_equal(np.asarray(got["x"]), g["x"])
    np.testing.assert_array_equal(np.asarray(got["y"]), g["y"])
    print(f"hier allgather {nodes}x{cores} backend={backend} ok")

    # ---- degenerate embeddings: a 1 x p hier broadcast equals the flat
    # circulant broadcast over the same devices.
    mesh1 = Mesh(np.array(jax.devices()[:p]).reshape(1, p), ("node", "core"))
    spec1 = NamedSharding(mesh1, P(("node", "core")))
    h1 = get_hier_comm(mesh1, "node", "core", backend=backend)
    arr = jax.device_put(jnp.asarray(state["w"]), spec1)
    a = np.asarray(h1.broadcast(arr, n_intra=3, root=1))
    b = np.asarray(circulant_broadcast(mesh1, "core", arr, n_blocks=3,
                                       root=1, backend=backend))
    np.testing.assert_array_equal(a, b)
    print(f"hier degenerate 1x{p} == flat backend={backend} ok")


def check_analysis(p, nodes, backend="jnp"):
    """Static plan audit of real *device* plans: build CollectivePlan /
    HierPlan objects on a live mesh and run repro.analysis.planaudit on
    their statics (the host-plane CLI covers host plans; this covers
    the jitted flavour's closed-over tables)."""
    from repro.analysis import audit_plan
    from repro.core.comm import get_comm
    from repro.core.hier import get_hier_comm

    mesh = make_mesh(p)
    comm = get_comm(mesh, "data", backend=backend)
    rng = np.random.default_rng(41)
    xs = {"w": sharded(mesh, jnp.asarray(
        rng.normal(size=(p, 12)).astype(np.float32)))}
    for kind in ("broadcast", "allgather", "reduce", "allreduce"):
        rooted = kind in ("broadcast", "reduce")
        plan = comm.plan(kind, xs, n_blocks=3,
                         root=p - 1 if rooted else 0)
        rep = audit_plan(plan)
        assert rep.ok, f"device {kind} plan failed audit:\n{rep.summary()}"
        assert rep.checked > 0, f"device {kind} audit was vacuous"
        print(f"analysis device {kind} p={p} backend={backend} ok")
    qplan = comm.plan("quantized_allreduce",
                      {"g": sharded(mesh, jnp.asarray(
                          rng.normal(size=(p, 512)).astype(np.float32)))},
                      qblock=256)
    rep = audit_plan(qplan)
    assert rep.ok, f"device quantized plan failed audit:\n{rep.summary()}"
    print(f"analysis device quantized_allreduce p={p} ok")

    cores = p // nodes
    hmesh = Mesh(np.array(jax.devices()[:p]).reshape(nodes, cores),
                 ("node", "core"))
    hc = get_hier_comm(hmesh, "node", "core", backend=backend)
    spec2d = NamedSharding(hmesh, P(("node", "core")))
    hxs = {"w": jax.device_put(jnp.asarray(
        rng.normal(size=(p, 10)).astype(np.float32)), spec2d)}
    for kind in ("broadcast", "reduce", "allreduce", "allgather"):
        rooted = kind in ("broadcast", "reduce")
        hplan = hc.plan(kind, hxs, n_inter=2, n_intra=2,
                        root=p - 1 if rooted else 0)
        rep = audit_plan(hplan)
        assert rep.ok, f"device hier {kind} failed audit:\n{rep.summary()}"
        print(f"analysis device hier {kind} {nodes}x{cores} ok")


def check_ring(p, elems=16):
    mesh = make_mesh(p)
    data = np.arange(p * elems, dtype=np.float32)
    x = sharded(mesh, jnp.asarray(data))
    out = jax.jit(lambda a: ring_allgather(mesh, "data", a))(x)
    np.testing.assert_allclose(np.asarray(out), data)
    print(f"ring p={p} ok")


def check_tracing(p, calls=3):
    """Each plan call writes one ``circulant.call`` host span with one
    ``circulant.validate`` and one ``circulant.execute`` nested in it,
    flat and hierarchical plans alike; for every kind each plan's static
    counters match the collective-permutes of its compiled HLO and their
    bytes, and the HLO carries the round-step and layout scopes."""
    import glob
    import tempfile

    from jax.profiler import ProfileData

    from repro.core import tracing
    from repro.core.comm import CirculantComm
    from repro.core.hier import HierComm
    from repro.launch.hlo_analysis import collective_stats

    mesh = make_mesh(p)
    comm = CirculantComm(mesh, "data")
    x = sharded(mesh, jnp.arange(p * 300, dtype=jnp.float32).reshape(p, 300))
    hmesh = Mesh(np.array(jax.devices()[:p]).reshape(2, p // 2),
                 ("node", "core"))
    hx = jax.device_put(x, NamedSharding(hmesh, P(("node", "core"))))
    hcomm = HierComm(hmesh, "node", "core")
    runs = [(comm.plan("allreduce", x, n_blocks=3), x),
            (comm.plan("quantized_allreduce", x, n_blocks=2), x),
            (hcomm.plan("allreduce", hx), hx)]
    rs = sharded(mesh, jnp.ones((p, 8 * p), jnp.int32))
    # 2048-element blocks: the jnp round step lays them out as tile stacks
    big = sharded(mesh, jnp.ones((p, 8192), jnp.float32))
    tiled = comm.plan("allreduce", big, n_blocks=4)
    assert tiled.tiled_leaves == 1 and runs[0][0].tiled_leaves == 0
    counted = runs + [(tiled, big),
        (comm.plan("broadcast", x, n_blocks=4, root=1), x),
        (comm.plan("allgather", x, n_blocks=3), x),
        (comm.plan("allgatherv", x, n_blocks=2,
                   sizes=[300 - 7 * j for j in range(p)]), x),
        (comm.plan("reduce_scatter", rs, n_blocks=3), rs),
        (CirculantComm(mesh, "data", backend="pallas").plan(
            "allreduce", x, n_blocks=3), x),
        (hcomm.plan("allgather", hx), hx),
        (hcomm.plan("broadcast", hx, root=1), hx)]
    for plan, arg in counted:
        text = jax.jit(plan).lower(arg).compile().as_text()
        stats = collective_stats(text)
        assert stats.ops_by_kind["collective-permute"] == plan.permutes, (
            plan.describe(), stats.ops_by_kind)
        assert stats.bytes_by_kind["collective-permute"] == plan.wire_bytes, (
            plan.describe(), stats.bytes_by_kind)
        for name in ("roundstep.", tracing.SPLIT, tracing.JOIN):
            assert name in text, (plan.kind, name)
        assert (tracing.SCALES in text) == (plan.kind == "quantized_allreduce")
    check_grad_sync_counters(mesh, p)
    for plan, arg in runs:
        jax.block_until_ready(plan(arg))           # compile outside the trace
    with tempfile.TemporaryDirectory() as logdir:
        jax.profiler.start_trace(logdir)
        try:
            for plan, arg in runs:
                for _ in range(calls):
                    jax.block_until_ready(plan(arg))
        finally:
            jax.profiler.stop_trace()
        path, = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
        spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                 for plane in ProfileData.from_file(path).planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for e in line.events
                 if e.name.startswith("circulant.")]
    outer = [s for s in spans if s[0] == tracing.CALL]
    assert len(outer) == calls * len(runs), spans
    for inner in (tracing.VALIDATE, tracing.EXECUTE):
        assert sum(s[0] == inner for s in spans) == len(outer), inner
        for _, a, b in outer:
            assert sum(s[0] == inner and a <= s[1] and s[2] <= b
                       for s in spans) == 1, (inner, a, b)
    print(f"tracing ok: {len(outer)} calls")


def check_grad_sync_counters(mesh, p):
    """The trainer's sync path (``compressed_grad_sync`` inside
    ``shard_map``, two buckets) builds no plan: its static counters
    ``grad_sync_counters`` equal the collective-permutes of its compiled
    HLO and their bytes, the scales' bytes those of the permutes under
    ``circulant.scales``, and no bucket's quantized slot tiles, as the
    HLO's flat reduce buffer shows."""
    import re

    from repro.core import tracing
    from repro.launch.hlo_analysis import collective_stats
    from repro.optim.compression import (
        compressed_grad_sync,
        grad_sync_counters,
        make_bucket_spec,
    )

    sizes = {"a": 1000, "b": 700}
    spec = make_bucket_spec(
        {k: jax.ShapeDtypeStruct((n,), jnp.float32) for k, n in sizes.items()},
        4 * 1000)
    assert spec.num_buckets == 2

    def body(a, b, ea, eb):
        mean, errs = compressed_grad_sync({"a": a[0], "b": b[0]},
                                          [ea[0], eb[0]], "data", p, spec)
        return mean["a"][None], mean["b"][None], errs[0][None], errs[1][None]

    sh = NamedSharding(mesh, P("data"))
    args = [jax.ShapeDtypeStruct((p, n), jnp.float32, sharding=sh)
            for n in (1000, 700, 1000, 700)]
    text = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("data"),) * 4,
        out_specs=(P("data"),) * 4)).lower(*args).compile().as_text()
    got = grad_sync_counters(spec, p)
    # the buckets' quantized slots stay flat, in whole qblocks
    width = -(-(-(-1000 // got.n_blocks)) // 256) * 256
    assert got.tiled_qslots == 0, got
    assert f"f32[1,{got.n_blocks + 2},{width}]" in text, (got, width)
    stats = collective_stats(text)
    assert stats.ops_by_kind["collective-permute"] == got.permutes, (
        got, stats.ops_by_kind)
    assert stats.bytes_by_kind["collective-permute"] == got.wire_bytes, (
        got, stats.bytes_by_kind)
    op = re.compile(r"collective-permute(-start)?\(")
    scales = collective_stats("\n".join(
        ln for ln in text.splitlines()
        if not op.search(ln) or tracing.SCALES in ln))
    assert scales.ops_by_kind["collective-permute"] == got.permutes // 2
    assert (scales.bytes_by_kind["collective-permute"]
            == got.scales_wire_bytes), (got, scales.bytes_by_kind)


def main(what, p, backend="jnp", nodes=2):
    if len(jax.devices()) < p:
        # Graceful skip (e.g. a backend that ignores the host-device
        # forcing flag): the caller maps this to pytest.skip.
        print(f"SKIP only {len(jax.devices())} device(s) available, need {p}")
        return
    if what == "analysis":
        assert p % nodes == 0, f"nodes={nodes} must divide p={p}"
        check_analysis(p, nodes, backend=backend)
        print("ALL OK")
        return
    if what == "hier":
        assert p % nodes == 0, f"nodes={nodes} must divide p={p}"
        check_hier(nodes, p // nodes, backend=backend)
        print("ALL OK")
        return
    if what in ("broadcast", "all"):
        for n in (1, 2, 3, 5, 8):
            check_broadcast(p, n, root=0, backend=backend)
        check_broadcast(p, 4, root=p // 2, backend=backend)
        check_broadcast(p, 4, root=p - 1, backend=backend)
        check_broadcast(p, 3, root=0, dtype=jnp.bfloat16, backend=backend)
        check_broadcast(p, 3, root=0, dtype=jnp.int32, backend=backend)
    if what in ("allgather", "all"):
        for n in (1, 2, 5, 8):
            check_allgather(p, n, backend=backend)
        check_allgather(p, 3, dtype=jnp.bfloat16, backend=backend)
    if what in ("allgatherv", "all"):
        rng = np.random.default_rng(3)
        check_allgatherv(p, 2, [10 * ((j % 3)) + 1 for j in range(p)],
                         backend=backend)
        # degenerate: one rank has everything
        check_allgatherv(p, 3, [600] + [1] * (p - 1), backend=backend)
        check_allgatherv(p, 2, list(rng.integers(1, 50, size=p)),
                         backend=backend)
    if what in ("ring", "all"):
        check_ring(p)
    if what in ("compressed", "all"):
        check_compressed_allreduce(p, backend=backend)
    if what == "gradsync":
        check_gradsync(p, backend=backend)
    if what == "gradsync_stream":
        check_gradsync_stream(p, backend=backend)
    if what in ("overlap", "all"):
        check_overlap(p, backend=backend)
    if what in ("restore", "all"):
        check_restore_broadcast(p)
    if what in ("reducescatter", "all"):
        check_reduce_scatter(p)
    if what in ("reduce", "all"):
        check_reduce(p, backend=backend)
    if what in ("allreduce", "all"):
        check_allreduce(p, backend=backend)
    if what in ("allbroadcast", "all"):
        check_allbroadcast(p)
    if what in ("comm", "all"):
        check_comm(p, backend=backend)
    if what == "tracing":
        check_tracing(p)
    print("ALL OK")


if __name__ == "__main__":
    main(what, p, backend, nodes)
