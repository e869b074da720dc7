"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def attention_ref(q, k, v, *, causal=True, window=None):
    """Naive attention.  q: [BH, Sq, hd]; k/v: [BH, Skv, hd(_v)]."""
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s / math.sqrt(q.shape[-1])
    qi = jnp.arange(q.shape[1])[:, None]
    kj = jnp.arange(k.shape[1])[None, :]
    mask = jnp.ones((q.shape[1], k.shape[1]), bool)
    if causal:
        mask = mask & (kj <= qi)
        if window is not None:
            mask = mask & (qi - kj < window)
    s = jnp.where(mask[None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", w, v.astype(jnp.float32)).astype(q.dtype)


def _take_slot(buffers, idx):
    """buffers[r, idx[r]] for every row r of [R, nslots, *slot]."""
    col = idx.reshape((-1,) + (1,) * (buffers.ndim - 1))
    return jnp.take_along_axis(buffers, col, axis=1)[:, 0]


def _rows(mask, msg):
    """[R] row mask -> broadcastable against [R, *slot] messages."""
    return mask.reshape((-1,) + (1,) * (msg.ndim - 1))


def block_pack_ref(buffers, idx):
    """buffers: [R, nslots, *slot]; idx: [R] int32 -> packed [R, *slot]."""
    return _take_slot(buffers, idx)


def block_unpack_ref(buffers, msg, idx):
    """Scatter msg rows into buffers at per-row slots."""
    return buffers.at[jnp.arange(buffers.shape[0]), idx].set(msg)


def block_shuffle_ref(buffers, msg, recv_idx, send_idx):
    """Fused unpack+pack oracle: write msg at recv slots, then read the
    send slots from the UPDATED buffer (pipeline: a round-t delivery may
    be the round-t+1 send).  Returns (new_buffers, out_msg)."""
    rows = jnp.arange(buffers.shape[0])
    buffers = buffers.at[rows, recv_idx].set(msg, mode="promise_in_bounds")
    out = _take_slot(buffers, send_idx)
    return buffers, out


def block_shuffle_staged_ref(buffers, msg, pre, recv_idx, send_idx):
    """Overlap-staged shuffle oracle: ``pre`` is the round-t+1 block
    packed from the PRE-update buffer (before round t's delivery
    landed).  Write msg at the recv slots; the outgoing message is msg
    where the pipeline case ``send == recv`` holds (the only slot the
    update changed) and ``pre`` everywhere else -- bit-exact vs
    :func:`block_shuffle_ref`.  Returns (new_buffers, out_msg)."""
    rows = jnp.arange(buffers.shape[0])
    buffers = buffers.at[rows, recv_idx].set(msg, mode="promise_in_bounds")
    out = jnp.where(_rows(recv_idx == send_idx, msg), msg, pre)
    return buffers, out


def block_acc_shuffle_staged_ref(buffers, msg, pre, acc_idx, fwd_idx,
                                 op="sum"):
    """Overlap-staged accumulate+capture/drain oracle: ``pre`` is the
    round-t+1 fwd block packed from the PRE-update buffer.  Accumulate
    msg into the acc slots; the captured output is the freshly combined
    value where ``fwd == acc`` (the clamped same-slot case) and ``pre``
    everywhere else, then the fwd slots drain to the op identity --
    bit-exact vs :func:`block_acc_shuffle_ref`.
    Returns (new_buffers, out_msg)."""
    from .reduce_ops import op_combine, op_identity

    combine = op_combine(op)
    rows = jnp.arange(buffers.shape[0])
    cur = _take_slot(buffers, acc_idx)
    combined = combine(cur, msg)
    buffers = buffers.at[rows, acc_idx].set(
        combined, mode="promise_in_bounds"
    )
    out = jnp.where(_rows(acc_idx == fwd_idx, pre), combined, pre)
    ident = op_identity(op, buffers.dtype)
    buffers = buffers.at[rows, fwd_idx].set(
        jnp.full_like(out, ident), mode="promise_in_bounds"
    )
    return buffers, out


def block_acc_shuffle_ref(buffers, msg, acc_idx, fwd_idx, op="sum"):
    """Fused accumulate+capture/drain oracle (capture-drain-accumulate
    order of docs/collectives.md): accumulate msg into the acc slots,
    capture the fwd slots from the updated buffer, then drain the fwd
    slots to the op identity.  Returns (new_buffers, out_msg)."""
    from .reduce_ops import op_combine, op_identity

    combine = op_combine(op)
    rows = jnp.arange(buffers.shape[0])
    cur = _take_slot(buffers, acc_idx)
    buffers = buffers.at[rows, acc_idx].set(
        combine(cur, msg), mode="promise_in_bounds"
    )
    out = _take_slot(buffers, fwd_idx)
    ident = op_identity(op, buffers.dtype)
    buffers = buffers.at[rows, fwd_idx].set(
        jnp.full_like(out, ident), mode="promise_in_bounds"
    )
    return buffers, out


def block_qacc_shuffle_ref(buffers, err, qmsg, smsg, acc_idx, fwd_idx):
    """Quantized accumulate+capture/drain oracle (sum only).

    The incoming message is int8 blocks ``qmsg`` [R, *slot] with
    per-QBLOCK scales ``smsg`` [R, nb] (nb * qb elements per slot):
    dequantize, accumulate into
    the acc slots of the f32 ``buffers`` [R, nslots, *slot], capture the fwd
    slots from the updated buffer, quantize the captured partial for the
    wire, record the requantization error into the matching slot of
    ``err`` [R, nslots, *slot], then drain the fwd slots to zero.

    Returns (new_buffers, new_err, out_q [R, *slot] int8, out_s [R, nb] f32).
    """
    from .quant_ops import dequant_blocks, quant_blocks, quant_error

    R, slot = buffers.shape[0], buffers.shape[2:]
    nb = smsg.shape[1]
    qb = math.prod(slot) // nb
    rows = jnp.arange(R)

    deq = dequant_blocks(
        qmsg.reshape(R * nb, qb), smsg.reshape(R * nb, 1)
    ).reshape((R,) + slot)
    cur = _take_slot(buffers, acc_idx)
    buffers = buffers.at[rows, acc_idx].set(
        cur + deq, mode="promise_in_bounds"
    )

    captured = _take_slot(buffers, fwd_idx)
    q, s = quant_blocks(captured.reshape(R * nb, qb))
    eps = quant_error(captured.reshape(R * nb, qb), q, s).reshape((R,) + slot)
    cur_e = _take_slot(err, fwd_idx)
    err = err.at[rows, fwd_idx].set(cur_e + eps, mode="promise_in_bounds")

    buffers = buffers.at[rows, fwd_idx].set(
        jnp.zeros_like(captured), mode="promise_in_bounds"
    )
    return buffers, err, q.reshape((R,) + slot), s.reshape(R, nb)


def ssd_ref(x, B_, C_, dt, A_log, D):
    """Sequential SSD recurrence oracle.  x: [BH, S, P]; B_/C_: [BH, S, N];
    dt: [BH, S]; A_log/D: scalars per row [BH]."""
    A = -jnp.exp(A_log)                                        # [BH]

    def step(s, inp):
        xt, bt, ct, dtt = inp                                  # [BH,P],[BH,N],[BH,N],[BH]
        a = jnp.exp(dtt * A)
        s = s * a[:, None, None] + dtt[:, None, None] * (
            bt[:, :, None] * xt[:, None, :]
        )
        y = jnp.einsum("bn,bnp->bp", ct, s)
        return s, y

    s0 = jnp.zeros((x.shape[0], B_.shape[-1], x.shape[-1]), jnp.float32)
    _, ys = jax.lax.scan(
        step, s0,
        (jnp.moveaxis(x, 1, 0).astype(jnp.float32),
         jnp.moveaxis(B_, 1, 0).astype(jnp.float32),
         jnp.moveaxis(C_, 1, 0).astype(jnp.float32),
         jnp.moveaxis(dt, 1, 0).astype(jnp.float32)),
    )
    y = jnp.moveaxis(ys, 0, 1)
    return y + x.astype(jnp.float32) * D[:, None, None]
