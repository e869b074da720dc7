"""int8 compression + quantized circulant allreduce: arithmetic and
data-plane certification (single process).

The centerpiece certifies the quantized-allreduce host data plane
bit-for-bit against an independent pure-NumPy replay of the schedule:
same slot tables, but every quantize / dequantize / accumulate done in
plain ``np.float32`` ops -- if the jnp oracle or the Pallas kernel
reorders, fuses (FMA) or widens any arithmetic, the comparison breaks
in the last bit.  Multi-device behaviour (shard_map, error-feedback
completeness under psum, trainer parity) lives in test_collectives.py
via tests/mp_worker.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.comm import host_plan
from repro.core.simulator import (
    replay_quantized_allreduce as np_quantized_allreduce,
)
from repro.optim.compression import (
    BLOCK,
    BucketSpec,
    block_nonfinite,
    bucketize,
    dequantize_int8,
    init_grad_sync_state,
    make_bucket_spec,
    quantize_int8,
    unbucketize,
)

@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("p,n", [(2, 1), (3, 2), (5, 4), (8, 2)])
def test_quantized_allreduce_bitexact_vs_numpy(backend, p, n):
    """Quantized circulant allreduce == independent NumPy replay,
    bit-for-bit, on both data-plane backends."""
    qb = 8
    plan = host_plan("quantized_allreduce", p, n, backend=backend,
                     qblock=qb)
    rng = np.random.default_rng(100 * p + n)
    # high dynamic range across quantization blocks
    vals = (rng.normal(size=(p, n, 3 * qb)) *
            10.0 ** rng.integers(-4, 5, size=(p, n, 1))).astype(np.float32)
    out, err = plan.run(vals)
    ref_out, ref_err = np_quantized_allreduce(plan, vals)
    np.testing.assert_array_equal(out, ref_out)
    np.testing.assert_array_equal(err, ref_err)
    # every rank's row identical; completeness vs the exact f32 sum
    for r in range(1, p):
        np.testing.assert_array_equal(out[r], out[0])
    exact = vals.astype(np.float64).sum(0)
    recon = out[0].astype(np.float64) + err.astype(np.float64).sum(0)
    resid = np.abs(recon - exact)
    tol = 1e-4 * np.maximum(np.abs(exact), np.abs(vals).max(0) * p) + 1e-7
    assert (resid <= tol).all(), resid.max()


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_quantized_allreduce_nonfinite_bitexact(backend):
    """NaN/inf lanes: flagged blocks come back all-NaN on every rank,
    error state stays finite, and jnp/pallas/NumPy still agree
    bit-for-bit (NaN positions included)."""
    p, n, qb = 3, 2, 8
    plan = host_plan("quantized_allreduce", p, n, backend=backend,
                     qblock=qb)
    rng = np.random.default_rng(3)
    vals = rng.normal(size=(p, n, 3 * qb)).astype(np.float32)
    vals[1, 0, qb + 2] = np.nan
    vals[0, 1, 2 * qb] = np.inf
    out, err = plan.run(vals)
    ref_out, ref_err = np_quantized_allreduce(plan, vals)
    np.testing.assert_array_equal(out, ref_out)
    np.testing.assert_array_equal(err, ref_err)
    assert np.isfinite(err).all()
    for r in range(p):
        assert np.isnan(out[r, 0, qb:2 * qb]).all()
        assert np.isnan(out[r, 1, 2 * qb:3 * qb]).all()
        assert np.isfinite(out[r, 0, :qb]).all()
        assert np.isfinite(out[r, 0, 2 * qb:]).all()


def test_host_plan_identity_and_validation():
    plan = host_plan("quantized_allreduce", 4, 2, qblock=8)
    assert host_plan("quantized_allreduce", 4, 2, qblock=8) is plan
    assert host_plan("quantized_allreduce", 4, 2, qblock=16) is not plan
    with pytest.raises(ValueError, match="qblock"):
        host_plan("broadcast", 4, 2, qblock=8)
    with pytest.raises(ValueError, match="sums"):
        host_plan("quantized_allreduce", 4, 2, op="max")


# ------------------------------------------ pad tail of the error vector


def _stacked_qallreduce(vals, n):
    """``circulant_qallreduce_body`` with the p ranks stacked under
    ``vmap`` (the rank stack's layout): ``(sums, errs)`` as [p, size]."""
    from repro.core.comm import circulant_qallreduce_body

    p = vals.shape[0]

    def body(x):
        sums, errs = circulant_qallreduce_body([x], "x", p, n_blocks=n)
        return sums[0], errs[0]

    out, err = jax.jit(jax.vmap(body, axis_name="x"))(jnp.asarray(vals))
    return np.asarray(out), np.asarray(err)


@pytest.mark.parametrize("inf", [False, True])
@pytest.mark.parametrize("p,n,size,slot", [
    (4, 2, 1000, (512,)),              # flat: 24 pad lanes in block 1
    (4, 3, 24271, (32, 256)),          # stack: a partial and a pad qblock
    (3, 3, 47674, (64, 256)),          # stack with a pad row of its own
])
def test_quantized_error_pad_tail_is_zero(p, n, size, slot, inf):
    """At a size that is no multiple of n * qblock, the error vector's
    tail past ``size`` is exactly zero after the quantized reduce and
    the root's requantization, also when an inf flags the block that
    holds the tail's first lanes; so the error returned for ``size``
    elements drops nothing, and exact == lossy + psum(err) holds."""
    from repro.core.roundstep import get_round_step

    assert get_round_step("jnp").slot_shape(-(-size // n), np.float32,
                                            256) == slot
    rng = np.random.default_rng(size + p)
    vals = (rng.normal(size=(p, size)) *
            10.0 ** rng.integers(-3, 4, size=(p, 1))).astype(np.float32)
    if inf:
        vals[1, size - 1] = np.inf
    out, err = _stacked_qallreduce(vals, n)
    # The same leaf zero padded to whole qblocks in each of its n blocks:
    # the same blocks and rounds, so its error past ``size`` is the tail
    # the first run cut.
    full = n * -(-size // (n * 256)) * 256
    out_f, err_f = _stacked_qallreduce(
        np.pad(vals, [(0, 0), (0, full - size)]), n)
    np.testing.assert_array_equal(err_f[:, size:], 0.0)
    np.testing.assert_array_equal(err_f[:, :size], err)
    np.testing.assert_array_equal(out_f[:, :size], out)
    assert np.isfinite(err).all()
    for r in range(1, p):
        np.testing.assert_array_equal(out[r], out[0])
    exact = vals.astype(np.float64).sum(0)
    finite = np.isfinite(out[0])       # a flagged block comes back NaN
    assert finite.all() != inf and np.isfinite(exact[finite]).all()
    recon = out[0].astype(np.float64) + err.astype(np.float64).sum(0)
    resid = np.abs(recon - exact)[finite]
    tol = (1e-4 * np.maximum(np.abs(exact), np.abs(vals).max(0) * p)
           + 1e-7)[finite]
    assert (resid <= tol).all(), resid.max()


@pytest.mark.parametrize("p,n,nq", [(4, 3, 63), (3, 5, 95)])
def test_tiled_quantized_leaf_matches_host_plan(p, n, nq):
    """A leaf whose quantized slot is a tile stack with a pad row (nq
    qblocks a block, nq + 1 rows) syncs bit-for-bit as the host data
    plan does on its [p, n, nq * qblock] blocks: each schedule block
    holds the qblocks a flat slot would, and the pad rows carry none."""
    from repro.core.roundstep import get_round_step

    bs = nq * 256
    assert get_round_step("jnp").slot_shape(bs, np.float32, 256) == (
        nq + 1, 256)
    rng = np.random.default_rng(nq)
    vals = (rng.normal(size=(p, n * bs)) *
            10.0 ** rng.integers(-3, 4, size=(p, 1))).astype(np.float32)
    out, err = _stacked_qallreduce(vals, n)
    h_out, h_err = host_plan("quantized_allreduce", p, n, qblock=256).run(
        vals.reshape(p, n, bs))
    np.testing.assert_array_equal(out, h_out.reshape(p, -1))
    np.testing.assert_array_equal(err, h_err.reshape(p, -1))


def test_grad_sync_counters_count_tiled_qslots():
    """The 25 MiB and 4 MiB buckets' quantized slots take the tile
    stack; a 300-element bucket's stays flat."""
    from repro.optim.compression import grad_sync_counters

    def tiled(elems):
        spec = make_bucket_spec(jax.ShapeDtypeStruct((elems,), jnp.float32),
                                4 * elems)
        return grad_sync_counters(spec, 4).tiled_qslots

    assert tiled(26214400 // 4) == 1
    assert tiled((4 << 20) // 4) == 1
    assert tiled(300) == 0


# ------------------------------------------------------------ quantize


def test_quantize_nonfinite_blocks():
    """A NaN or inf poisons exactly its own block -- flagged via a NaN
    scale, dequantizing to all-NaN -- and neighbouring blocks are
    untouched; finite lanes of the bad block still quantize sanely."""
    rng = np.random.default_rng(0)
    v = rng.normal(size=(4 * BLOCK,)).astype(np.float32)
    v[BLOCK + 3] = np.nan
    v[2 * BLOCK + 7] = -np.inf
    q, s = jax.jit(quantize_int8)(jnp.asarray(v))
    flags = np.asarray(block_nonfinite(s)).reshape(-1)
    assert flags.tolist() == [False, True, True, False]
    dq = np.asarray(jax.jit(dequantize_int8)(q, s))
    assert np.isnan(dq[BLOCK:3 * BLOCK]).all()
    assert np.isfinite(dq[:BLOCK]).all() and np.isfinite(dq[3 * BLOCK:]).all()
    # clean blocks round-trip within one quantization step
    assert np.abs(dq[:BLOCK] - v[:BLOCK]).max() <= np.abs(v[:BLOCK]).max() / 127
    # the bad block's finite lanes were quantized against the finite
    # amax (wire content preserved modulo the flag)
    qb = np.asarray(q).reshape(4, BLOCK)[1]
    fin = np.isfinite(v[BLOCK:2 * BLOCK])
    assert np.abs(qb[fin]).max() > 0


def test_quantize_zero_and_tiny_blocks():
    """All-zero and denormal-scale blocks: the 1e-12 scale floor must
    yield exact zeros (not garbage) and zero error."""
    v = np.zeros((2 * BLOCK,), np.float32)
    v[BLOCK:] = 1e-30
    q, s = quantize_int8(jnp.asarray(v))
    assert not np.asarray(block_nonfinite(s)).any()
    dq = np.asarray(dequantize_int8(q, s))
    np.testing.assert_array_equal(dq[:BLOCK], 0.0)
    # sub-floor magnitudes quantize to exact zero (their full value is
    # the quantization error, recovered by the feedback loop)
    np.testing.assert_array_equal(dq[BLOCK:], 0.0)


def test_error_state_is_f32_for_low_precision_params():
    params = {"a": jnp.zeros((3, 4), jnp.bfloat16),
              "b": jnp.zeros((7,), jnp.float16)}
    spec = make_bucket_spec(params)
    err = init_grad_sync_state(spec)
    assert all(e.dtype == jnp.float32 for e in err)
    assert all(f.dtype == jnp.float32 for f in bucketize(params, spec))


# ------------------------------------------------------------- buckets


def test_bucket_spec_and_roundtrip_ragged():
    shapes = {"w1": (17, 9), "b1": (9,), "w2": (9, 23), "b2": (23,),
              "scalar": ()}
    params = {k: jnp.zeros(s, jnp.float32) for k, s in shapes.items()}
    spec = make_bucket_spec(params, bucket_bytes=4 * 150)
    assert isinstance(spec, BucketSpec)
    assert spec.num_buckets > 1
    assert sum(spec.bucket_sizes) == sum(
        int(np.prod(s)) if s else 1 for s in shapes.values())
    assert hash(spec) == hash(make_bucket_spec(params, bucket_bytes=4 * 150))

    rng = np.random.default_rng(5)
    tree = {k: jnp.asarray(rng.normal(size=s).astype(np.float32))
            for k, s in shapes.items()}
    flats = bucketize(tree, spec)
    assert [f.shape[0] for f in flats] == list(spec.bucket_sizes)
    back, deltas = unbucketize(flats, spec, tree)
    for k in shapes:
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(tree[k]))
    assert all(not np.asarray(d).any() for d in deltas)


def test_bucket_oversized_leaf_gets_own_bucket():
    # dict leaves flatten in key order: huge, small, tail
    params = {"small": jnp.zeros((10,)), "huge": jnp.zeros((1000,)),
              "tail": jnp.zeros((5,))}
    spec = make_bucket_spec(params, bucket_bytes=4 * 64)
    assert spec.num_buckets == 2
    assert spec.bucket_sizes == (1000, 15)
    assert spec.assignment == (0, 1, 1)


def test_unbucketize_downcast_delta():
    """bf16 leaves: the downcast loss lands in the delta vectors (the
    error-feedback hook), and cast + delta reconstructs f32 exactly."""
    tree = {"x": jnp.zeros((300,), jnp.bfloat16)}
    spec = make_bucket_spec(tree)
    rng = np.random.default_rng(9)
    flat = jnp.asarray(rng.normal(size=300).astype(np.float32))
    out, deltas = unbucketize([flat], spec, tree)
    assert out["x"].dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out["x"], np.float32) + np.asarray(deltas[0]),
        np.asarray(flat), rtol=0, atol=0)
    assert np.asarray(deltas[0]).any()


def test_bucketize_validates_leaf_count():
    spec = make_bucket_spec({"a": jnp.zeros((4,))})
    with pytest.raises(ValueError, match="leaves"):
        bucketize({"a": jnp.zeros((4,)), "b": jnp.zeros((4,))}, spec)
