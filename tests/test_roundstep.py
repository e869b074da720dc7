"""Backend conformance for the pluggable round-step data plane.

Two layers, both single-process (no multidevice marker -- this is the
schedule-stack fast lane's coverage of the Pallas path):

  1. kernel-level: the fused Pallas kernels (interpret mode) agree
     bit-exactly with the jnp reference backend on random slot plans,
     including the equal-slot pipeline cases, across dtypes and ops;
  2. collective-level: ``simulate_*`` with ``backend=`` executes the
     real round-step data plane over all p ranks and asserts bit-exact
     agreement with the message-passing NumPy reference, over the
     engine-test edge cases (p = 1, powers of two, odd p) for sum/max
     on int and float dtypes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.comm import host_plan
from repro.core.roundstep import get_round_step
from repro.kernels import layout
from repro.core.simulator import (
    simulate_allbroadcast,
    simulate_allreduce,
    simulate_broadcast,
    simulate_reduce,
)

RNG = np.random.default_rng(7)

# The p=1 / power-of-two / odd edge cases of tests/test_engine.py.
EDGE_PS = [1, 2, 3, 4, 5, 8, 11, 16, 32, 36]
BACKENDS = ["jnp", "pallas"]


def _rand(shape, dtype):
    if np.issubdtype(np.dtype(dtype), np.integer):
        return np.asarray(RNG.integers(-100, 100, size=shape), dtype)
    return np.asarray(RNG.normal(size=shape), dtype)


# ------------------------------------------------------- kernel level


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
@pytest.mark.parametrize("R,ns,bs", [(1, 4, 8), (8, 6, 16), (17, 9, 4)])
def test_shuffle_backends_bitexact(dtype, R, ns, bs):
    buf = jnp.asarray(_rand((R, ns, bs), dtype))
    msg = jnp.asarray(_rand((R, bs), dtype))
    recv = jnp.asarray(RNG.integers(0, ns, size=R), jnp.int32)
    send = jnp.asarray(RNG.integers(0, ns, size=R), jnp.int32)
    # force the pipeline case (send what was just received) on row 0
    send = send.at[0].set(recv[0])
    jstep, pstep = get_round_step("jnp"), get_round_step("pallas")
    jb, jm = jstep.shuffle(buf, msg, recv, send)
    pb, pm = pstep.shuffle(buf, msg, recv, send)
    np.testing.assert_array_equal(np.asarray(jb), np.asarray(pb))
    np.testing.assert_array_equal(np.asarray(jm), np.asarray(pm))
    # pack/unpack primitives agree too
    np.testing.assert_array_equal(
        np.asarray(jstep.pack(buf, send)), np.asarray(pstep.pack(buf, send))
    )
    np.testing.assert_array_equal(
        np.asarray(jstep.unpack(buf, msg, recv)),
        np.asarray(pstep.unpack(buf, msg, recv)),
    )


@pytest.mark.parametrize("op", ["sum", "max"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
@pytest.mark.parametrize("R,ns,bs", [(1, 4, 8), (8, 6, 16)])
def test_acc_shuffle_backends_bitexact(op, dtype, R, ns, bs):
    buf = jnp.asarray(_rand((R, ns, bs), dtype))
    msg = jnp.asarray(_rand((R, bs), dtype))
    acc = jnp.asarray(RNG.integers(0, ns, size=R), jnp.int32)
    fwd = jnp.asarray(RNG.integers(0, ns, size=R), jnp.int32)
    # force the clamped same-slot case (capture the just-accumulated
    # partial, then drain it) on row 0
    fwd = fwd.at[0].set(acc[0])
    jstep, pstep = get_round_step("jnp"), get_round_step("pallas")
    jb, jm = jstep.acc_shuffle(buf, msg, acc, fwd, op=op)
    pb, pm = pstep.acc_shuffle(buf, msg, acc, fwd, op=op)
    np.testing.assert_array_equal(np.asarray(jb), np.asarray(pb))
    np.testing.assert_array_equal(np.asarray(jm), np.asarray(pm))


def test_acc_shuffle_semantics():
    """The fused step implements accumulate -> capture -> drain."""
    buf = jnp.asarray(np.arange(2 * 3 * 2, dtype=np.int32).reshape(2, 3, 2))
    msg = jnp.asarray(np.full((2, 2), 10, np.int32))
    acc = jnp.asarray([0, 1], jnp.int32)
    fwd = jnp.asarray([0, 2], jnp.int32)
    for backend in BACKENDS:
        nb, out = get_round_step(backend).acc_shuffle(buf, msg, acc, fwd)
        nb, out = np.asarray(nb), np.asarray(out)
        # row 0: acc == fwd -> capture sees the accumulated value, slot drained
        assert np.array_equal(out[0], [0 + 10, 1 + 10])
        assert np.array_equal(nb[0, 0], [0, 0])
        # row 1: accumulate into slot 1, capture+drain slot 2
        assert np.array_equal(nb[1, 1], [8 + 10, 9 + 10])
        assert np.array_equal(out[1], [10, 11])
        assert np.array_equal(nb[1, 2], [0, 0])


# ------------------------------------------------ jnp slot layout rule


@pytest.mark.parametrize("bs,dtype,qblock,shape", [
    (-(-(26214400 // 4) // 23), jnp.float32, None, (2232, 128)),  # 25 MiB/23
    ((4096 // 4) // 4, jnp.float32, None, (256,)),                # 4 KiB/4
    (31000, jnp.float32, None, (248, 128)),    # pads 744 <= 31000/32
    (30721, jnp.float32, None, (30721,)),      # pads 1023 > 30721/32
    (32768, jnp.bfloat16, None, (256, 128)),   # 16-row tiles, no pad
    (2049, jnp.bfloat16, None, (2049,)),       # a 32x128 stack would pad 2047
    (209920, jnp.int8, None, (1664, 128)),     # 32-row tiles, +1.5%
    (1 << 20, jnp.float32, 256, (4096, 256)),  # quantized: one qblock a row
    (300, jnp.float32, 256, (512,)),           # flat, in whole qblocks
    (209716, jnp.float32, 256, (832, 256)),    # 4 MiB/5: pads 3276
    (595782, jnp.float32, 256, (2336, 256)),   # 25 MiB/11: 8 pad rows
    (7 * 256, jnp.float32, 256, (7 * 256,)),   # 32 rows would pad 25 of 32
    (1 << 16, jnp.float32, 64, (1 << 16,)),    # 64-lane rows pad to 128
])
def test_jnp_slot_rule(bs, dtype, qblock, shape):
    """Large slots take the tile stack, plain or quantized; small ones
    stay flat."""
    assert get_round_step("jnp").slot_shape(bs, dtype, qblock) == shape


def test_jnp_slot_rule_64bit_under_x64():
    """64-bit values have no TPU tile; the stack takes 8 rows, as in the
    Pallas interpret path."""
    step = get_round_step("jnp")
    with jax.enable_x64(True):
        assert layout.sublanes(jnp.float64) == 8
        assert step.slot_shape(4096, jnp.float64) == (32, 128)
        assert step.slot_shape(100, jnp.int64) == (100,)


JNP_METHODS = ["pack", "unpack", "shuffle", "shuffle_staged", "acc_shuffle",
               "acc_shuffle_staged"]


def _run_method(step, method, buf, msg, a, b):
    if method == "pack":
        return (step.pack(buf, b),)
    if method == "unpack":
        return (step.unpack(buf, msg, a),)
    if method == "shuffle":
        return step.shuffle(buf, msg, a, b)
    if method == "shuffle_staged":
        return step.shuffle_staged(buf, msg, step.pack(buf, b), a, b)
    if method == "acc_shuffle":
        return step.acc_shuffle(buf, msg, a, b)
    return step.acc_shuffle_staged(buf, msg, step.pack(buf, b), a, b)


@pytest.mark.parametrize("method,dtype", [
    (m, d) for m in JNP_METHODS
    for d in (jnp.float32, jnp.bfloat16, jnp.int32, jnp.int8)]
    + [("qacc_shuffle", jnp.float32)])
def test_jnp_methods_bitexact_flat_vs_tiled(method, dtype):
    """Every jnp method gives the same blocks on a flat and on a tiled
    buffer holding them; R equals the tile's rows so a row mask that
    broadcasts against the wrong axis shows.  The quantized step runs on
    a ``(rows, qblock)`` stack against whole flat qblocks."""
    if method == "qacc_shuffle":
        return _check_qacc_flat_vs_tiled()
    bs = 1000
    rows, lanes = layout.slot_shape(bs, dtype)
    R, ns = rows, 6
    pad = rows * lanes - bs
    flat_buf = jnp.asarray(_rand((R, ns, bs), dtype))
    flat_msg = jnp.asarray(_rand((R, bs), dtype))
    a = jnp.asarray(RNG.integers(0, ns, size=R), jnp.int32)
    b = jnp.asarray(RNG.integers(0, ns, size=R), jnp.int32)
    b = b.at[0].set(a[0])   # the pipeline / same-slot case on row 0

    def tiled(x):
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
        return x.reshape(x.shape[:-1] + (rows, lanes))

    step = get_round_step("jnp")
    flat = _run_method(step, method, flat_buf, flat_msg, a, b)
    tile = _run_method(step, method, tiled(flat_buf), tiled(flat_msg), a, b)
    for f, t in zip(flat, tile):
        assert t.shape == f.shape[:-1] + (rows, lanes)
        t = np.asarray(t).reshape(t.shape[:-2] + (-1,))
        np.testing.assert_array_equal(t[..., :bs], np.asarray(f))
        # the sum identity is zero, so the padding lanes stay zero
        assert not np.any(t[..., bs:])


def _check_qacc_flat_vs_tiled():
    """qacc_shuffle on whole flat qblocks and on their tile stack: the
    buffers, the error, the int8 message and the scales bit-identical;
    the stack's pad rows stay zero and their scales floor."""
    from repro.kernels.quant_ops import SCALE_FLOOR

    qb, bs = 256, 1000
    rows, _ = layout.slot_shape(bs, jnp.float32, qb)
    nb = -(-bs // qb)                          # 4 blocks; the stack has 32
    R, ns = rows, 6

    def qrand(shape, dtype):
        x = _rand(shape[:-1] + (bs,), dtype)   # real lanes, zero tail
        return np.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, nb * qb - bs)])

    flat = [qrand((R, ns, 0), np.float32), qrand((R, ns, 0), np.float32),
            np.clip(qrand((R, 0), np.int32), -127, 127).astype(np.int8),
            np.abs(_rand((R, nb), np.float32))]
    flat[1] *= 1e-3                            # an error state in flight
    a = jnp.asarray(RNG.integers(0, ns, size=R), jnp.int32)
    b = jnp.asarray(RNG.integers(0, ns, size=R), jnp.int32)
    b = b.at[0].set(a[0])   # the same-slot case on row 0

    def tiled(x, width=qb):
        x = np.pad(x, [(0, 0)] * (x.ndim - 1)
                   + [(0, rows * width - x.shape[-1])])
        return x.reshape(x.shape[:-1] + ((rows, width) if width > 1
                                          else (rows,)))

    step = get_round_step("jnp")
    got_f = step.qacc_shuffle(*map(jnp.asarray, flat), a, b)
    got_t = step.qacc_shuffle(*map(jnp.asarray, (
        tiled(flat[0]), tiled(flat[1]), tiled(flat[2]),
        tiled(flat[3], 1))), a, b)
    for f, t in zip(got_f[:3], got_t[:3]):     # buffers, error, int8
        assert t.shape == f.shape[:-1] + (rows, qb)
        t = np.asarray(t).reshape(t.shape[:-2] + (-1,))
        np.testing.assert_array_equal(t[..., :nb * qb], np.asarray(f))
        assert not np.any(t[..., bs:])         # pad lanes zero
    s_f, s_t = np.asarray(got_f[3]), np.asarray(got_t[3])
    assert s_t.shape == (R, rows)
    np.testing.assert_array_equal(s_t[:, :nb], s_f)
    np.testing.assert_array_equal(s_t[:, nb:], np.float32(SCALE_FLOOR))


def test_tiled_leaves_counts_tile_stacked_leaves():
    """A ddp-shaped plan (25 MiB f32 at p=4, n=23) lays its one leaf out
    as a tile stack; a 4 KiB plan keeps it flat; the quantized plan's
    int8 broadcast leaf tiles; Pallas tiles every leaf."""
    from repro.core.comm import _plan_messages, _tiled_leaves, payload_spec

    def count(kind, leaves, n, backend="jnp", qblock=None):
        spec = payload_spec([jax.ShapeDtypeStruct(s, d) for s, d in leaves])
        return _tiled_leaves(_plan_messages(
            kind, spec, 4, n, get_round_step(backend), qblock, None))

    ddp = ((4, 26214400 // 4), jnp.float32)
    small = ((4, 4096 // 4), jnp.float32)
    assert count("allreduce", [ddp], 23) == 1
    assert count("allreduce", [small], 4) == 0
    assert count("allreduce", [ddp, small], 23) == 1
    assert count("quantized_allreduce", [((4, 1 << 20), jnp.float32)], 5,
                 qblock=256) == 1
    assert count("allreduce", [small], 4, backend="pallas") == 1


def test_unknown_backend_raises():
    with pytest.raises(ValueError, match="backend"):
        get_round_step("cuda")


@pytest.mark.parametrize("backend", BACKENDS)
def test_unknown_op_raises(backend):
    """Both backends validate the reduction op instead of silently
    falling back (shared registry: repro.kernels.reduce_ops)."""
    buf = jnp.zeros((2, 3, 4), jnp.float32)
    msg = jnp.zeros((2, 4), jnp.float32)
    idx = jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError, match="reduction op"):
        get_round_step(backend).acc_shuffle(buf, msg, idx, idx, op="min")


# --------------------------------------------------- collective level


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("p", EDGE_PS)
def test_simulate_broadcast_certifies_backend(backend, p):
    for n in (1, 3, 5):
        for root in sorted({0, p - 1}):
            res = simulate_broadcast(p, n, root=root, backend=backend)
            assert res.rounds == res.optimal_rounds
            assert res.backend == backend


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("p", EDGE_PS)
def test_simulate_reduce_certifies_backend(backend, p):
    """Bit-exact sum/max on int64 and float64 values, every edge p."""
    rng = np.random.default_rng(p)
    for n in (1, 4):
        ivals = rng.integers(-(1 << 31), 1 << 31, size=(p, n)).astype(np.int64)
        fvals = rng.normal(size=(p, n))
        for op, vals in [("+", ivals), ("+", fvals),
                         ("max", ivals), ("max", fvals)]:
            res = simulate_reduce(p, n, root=p - 1, op=op, values=vals,
                                  backend=backend)
            assert res.rounds == res.optimal_rounds


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("p", [1, 2, 4, 5, 8, 16])
def test_simulate_allreduce_certifies_backend(backend, p):
    rng = np.random.default_rng(p * 3 + 1)
    for n in (1, 4):
        vals = rng.normal(size=(p, n))
        res = simulate_allreduce(p, n, values=vals, backend=backend)
        assert res.rounds == res.optimal_rounds
        ivals = rng.integers(-(1 << 31), 1 << 31, size=(p, n)).astype(np.int64)
        simulate_allreduce(p, n, values=ivals, op="max", backend=backend)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("p", [1, 2, 4, 8, 11])
def test_simulate_allbroadcast_certifies_backend(backend, p):
    for n in (1, 3):
        res = simulate_allbroadcast(p, n, backend=backend)
        assert res.rounds == res.optimal_rounds


# --------------------------------------- data planes agree across backends


@pytest.mark.parametrize("p", [2, 8, 13])
def test_dataplanes_bitexact_across_backends(p):
    """Beyond certifying each backend against the reference: the two
    backends produce identical buffers on identical inputs (float sums
    included -- same accumulation order)."""
    rng = np.random.default_rng(p)
    n = 4
    bvals = rng.normal(size=(n,))
    assert np.array_equal(
        host_plan("broadcast", p, n, root=0, backend="jnp").run(bvals),
        host_plan("broadcast", p, n, root=0, backend="pallas").run(bvals))
    gvals = rng.normal(size=(p, n))
    assert np.array_equal(
        host_plan("allgather", p, n, backend="jnp").run(gvals),
        host_plan("allgather", p, n, backend="pallas").run(gvals))
    for op in ("sum", "max"):
        assert np.array_equal(
            host_plan("reduce", p, n, root=p - 1, op=op,
                      backend="jnp").run(gvals),
            host_plan("reduce", p, n, root=p - 1, op=op,
                      backend="pallas").run(gvals),
        )
