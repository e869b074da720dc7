"""Schedule-driven block data-plane Pallas kernels (the per-round hot path).

Every collective in the family runs the same per-round inner step on its
block buffers (paper Algorithms 1-2 and the reversed reduction of
arXiv:2407.18004):

  * broadcast family -- ``pack`` one block per row into the outgoing
    message, exchange, ``unpack`` the incoming message into one slot per
    row;
  * reduce family -- capture the forwarded partial, drain its slot to
    the op identity, exchange, ``accumulate`` the incoming partial.

The block *selection* is the schedule: per-round int32 index vectors
known before the kernel runs but data-dependent per rank / per root row.
``PrefetchScalarGridSpec`` passes them as scalar-prefetch arguments so
every BlockSpec index_map can pick which HBM block to DMA into VMEM --
the pack/unpack becomes pure DMA scheduling with zero real compute,
exactly the paper's "packing ... bounded by the total size of all
buffers" requirement.

**Slot layout.**  The TPU compiler only accepts blocks whose last two
dimensions are multiples of the dtype's native tile (8x128 for 32-bit,
16x128 for 16-bit, 32x128 for 8-bit values) or span the whole array.
A buffer slot is therefore laid out as a ``[rows, lanes]`` tile stack:
buffers are ``[R, nslots, rows, lanes]`` and messages ``[R, rows,
lanes]`` (:func:`slot_shape`, from :mod:`repro.kernels.layout`, gives
the layout for a block of ``bs`` elements; plans hold their buffers in
it for every round).  The grid is
``(R, tiles)`` -- or ``(R, tiles, 2)`` for the two-step accumulate/drain
kernels -- where each grid point moves one ``[tile_rows, lanes]`` tile
of at most :data:`MAX_BLOCK_BYTES`, so every kernel's double-buffered
operands stay well inside the default scoped VMEM.  Flat ``[R, nslots,
bs]`` buffers are accepted too: they are zero-padded to the tile and
viewed in the slot layout for the call (a relayout per call, for tests
and one-off use; the plans never take that path).

Two *fused* kernels cover the steady state with one ``pallas_call`` per
round instead of two:

  * :func:`block_shuffle` -- unpack round t's received message, then
    pack round t+1's outgoing block from the *updated* buffer (the
    pipeline case "forward next what you just received" falls out of the
    in-kernel write-then-select ordering);
  * :func:`block_acc_shuffle` -- accumulate round t's incoming partial
    (sum/max with dtype identities), then capture round t+1's forwarded
    partial and drain its slot to the identity
    (capture-drain-accumulate, see docs/collectives.md).

The kernels compile for the TPU (``interpret=False`` there) and run
under ``interpret=True`` elsewhere, bit-exactly against the jnp
reference backend (:mod:`repro.core.roundstep`).  The fused kernels pass
the buffer twice (one read-only operand, one aliased to the output) so
no in-kernel value ever depends on reading back a block written earlier
in the same grid: the pipelined DMAs of the compiled kernel and the
sequential interpreter see the same values (proved per schedule by
:mod:`repro.analysis.kernelaudit`).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Slot layout arithmetic, shared with the jnp backend.
from .layout import LANES, QROWS, slot_shape, sublanes, tileable
# Single source for combine/identity semantics across kernels, the jnp
# oracles and the collectives (re-exported here for consumers that only
# know the kernel module).
from .quant_ops import dequant_blocks, quant_blocks, quant_error
from .reduce_ops import op_combine, op_identity

#: Upper bound on one operand block; with double buffering and the
#: fused kernels' five or so operands this keeps a kernel within a few
#: MiB of v5e's 16 MiB default scoped VMEM.
MAX_BLOCK_BYTES = 512 * 1024

_SQ = pl.Squeezed()
# Lane-tile index of every block: an explicit int32 so the index maps
# stay 32-bit when traced under ``jax.enable_x64`` (the host plans'
# certification mode), which Mosaic requires.
_LANE0 = np.int32(0)


def default_interpret() -> bool:
    """Auto-detected interpret mode: compiled on TPU, interpreted elsewhere."""
    return jax.default_backend() != "tpu"


def _resolve(interpret):
    return default_interpret() if interpret is None else interpret


# ----------------------------------------------------------- slot layout


def row_tile(rows: int, lanes: int, dtype, unit: Optional[int] = None) -> int:
    """Rows per grid tile: the largest divisor of ``rows`` that is a
    multiple of ``unit`` (default the dtype's sublane tile) and keeps
    one block within :data:`MAX_BLOCK_BYTES`.  A row count that is not a
    multiple of ``unit`` is one whole-slot tile (legal as a full-array
    block dimension)."""
    unit = sublanes(dtype) if unit is None else unit
    if rows % unit:
        return rows
    cap = max(unit, MAX_BLOCK_BYTES // (lanes * np.dtype(dtype).itemsize))
    best = unit
    for d in range(unit, min(rows, cap) + 1, unit):
        if rows % d == 0:
            best = d
    return best


def _to_tiles(x, shape):
    """[..., bs] -> [..., *shape], zero padding the tail of each slot."""
    bs, elems = x.shape[-1], math.prod(shape)
    if elems != bs:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, elems - bs)])
    return x.reshape(x.shape[:-1] + tuple(shape))


def _from_tiles(x, bs):
    """Inverse of :func:`_to_tiles`: [..., rows, lanes] -> [..., bs]."""
    return x.reshape(x.shape[:-2] + (-1,))[..., :bs]


def _on_flat(kernel, buffers, msgs, *idx, **kw):
    """Run a tiled kernel on flat [R, nslots, bs] buffers and [R, bs]
    messages: pad each slot to the tile, call, and flatten back."""
    bs = buffers.shape[-1]
    shape = slot_shape(bs, buffers.dtype)
    out = kernel(_to_tiles(buffers, shape),
                 *(_to_tiles(m, shape) for m in msgs), *idx, **kw)
    if isinstance(out, (tuple, list)):
        return tuple(_from_tiles(o, bs) for o in out)
    return _from_tiles(out, bs)


def _grid_tiles(buffers, unit: Optional[int] = None) -> Tuple[int, int]:
    """(tile_rows, tiles) for tiled [R, nslots, rows, lanes] buffers."""
    rows, lanes = buffers.shape[-2:]
    tr = row_tile(rows, lanes, buffers.dtype, unit)
    return tr, rows // tr


# ----------------------------------------------------------- index maps
#
# Every BlockSpec index map is a named module-level function so the
# static race detector (repro.analysis.kernelaudit) can evaluate the
# SAME map objects the pallas_call was built with over the whole grid.
# 1-step kernels get (r, j, *prefetch_refs); the two-step
# accumulate/drain kernels get (r, j, s, *prefetch_refs) with j the row
# tile and s the sequential sub-round.  Slot blocks address
# (row, slot, tile, 0) of [R, nslots, rows, lanes]; message blocks
# address (row, tile, 0) of [R, rows, lanes].


def _row_map(r, j, *rest):
    """Message tile of any kernel (pack out, unpack in, fused in/out)."""
    return (r, j, _LANE0)


def _slot_map1(r, j, idx_ref):
    """Prefetched-slot tile of the 1-prefetch kernels (pack/unpack)."""
    return (r, idx_ref[r], j, _LANE0)


def _send_map(r, j, ri, si):
    """Read-only send-slot tile of the shuffle kernel (pre-update)."""
    return (r, si[r], j, _LANE0)


def _recv_map(r, j, ri, si):
    """Recv-slot tile of the shuffle kernels (aliased, overwritten)."""
    return (r, ri[r], j, _LANE0)


def _fwd_map(r, j, s, ai, fi):
    """Fwd-slot tile of the accumulate/drain kernels (captured and, in
    the qacc error path, read-modify-written)."""
    return (r, fi[r], j, _LANE0)


def _step_map(r, j, s, ai, fi):
    """Aliased buffer tile of the accumulate/drain kernels: the acc
    slot at s == 0, the fwd slot at s == 1 (the drain)."""
    return (r, jnp.where(s == 0, ai[r], fi[r]), j, _LANE0)


def _slot_spec(tr, lanes, index_map):
    """One [tr, lanes] tile of a [R, nslots, rows, lanes] buffer."""
    return pl.BlockSpec((_SQ, _SQ, tr, lanes), index_map)


def _row_spec(tr, lanes, index_map=_row_map):
    """One [tr, lanes] tile of a [R, rows, lanes] message."""
    return pl.BlockSpec((_SQ, tr, lanes), index_map)


# Pallas input_output_aliases, operand-indexed INCLUDING the scalar
# prefetch arguments; module-level so the audit reads the exact dicts
# the calls pass.
UNPACK_ALIASES = {2: 0}      # buffers (3rd operand) -> output
SHUFFLE_ALIASES = {4: 0}     # 2nd buffer operand -> new_buffers
ACC_ALIASES = {4: 0}         # 2nd buffer operand -> new_buffers
QACC_ALIASES = {5: 0, 6: 1}  # 2nd buffer operand -> new_buffers, err -> new_err
SHUFFLE_STAGED_ALIASES = {4: 0}  # buffers operand -> new_buffers
ACC_STAGED_ALIASES = {4: 0}      # buffers operand -> new_buffers


# ------------------------------------------------------------------- pack


def _pack_kernel(idx_ref, buf_ref, out_ref):
    # the interesting work happened in the index_map DMA; just copy VMEM->VMEM
    del idx_ref
    out_ref[...] = buf_ref[...]


def block_pack(buffers: jnp.ndarray, idx: jnp.ndarray, *, interpret=None):
    """buffers: [R, nslots, rows, lanes] (or flat [R, nslots, bs]);
    idx: [R] int32 slot per row -> [R, rows, lanes] (or [R, bs]).

    Row r of the output is buffers[r, idx[r]]; the slot choice is the
    send schedule column for the round.
    """
    if buffers.ndim == 3:
        return _on_flat(block_pack, buffers, (), idx, interpret=interpret)
    R, nslots, rows, lanes = buffers.shape
    tr, T = _grid_tiles(buffers)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R, T),
        in_specs=[_slot_spec(tr, lanes, _slot_map1)],
        out_specs=_row_spec(tr, lanes),
    )
    return pl.pallas_call(
        _pack_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, rows, lanes), buffers.dtype),
        interpret=_resolve(interpret),
    )(idx.astype(jnp.int32), buffers)


# ----------------------------------------------------------------- unpack


def _unpack_kernel(idx_ref, msg_ref, buf_ref, out_ref):
    del idx_ref, buf_ref  # aliased with the output; untouched slots keep contents
    out_ref[...] = msg_ref[...]


def block_unpack(buffers: jnp.ndarray, msg: jnp.ndarray, idx: jnp.ndarray,
                 *, interpret=None):
    """Scatter msg rows into per-row slots: buffers[r, idx[r]] = msg[r].

    Implemented with an input-output alias so untouched slots keep their
    contents (the receive schedule only writes one slot per round).
    """
    if buffers.ndim == 3:
        return _on_flat(block_unpack, buffers, (msg,), idx,
                        interpret=interpret)
    R, nslots, rows, lanes = buffers.shape
    tr, T = _grid_tiles(buffers)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R, T),
        in_specs=[
            _row_spec(tr, lanes),
            _slot_spec(tr, lanes, _slot_map1),
        ],
        out_specs=_slot_spec(tr, lanes, _slot_map1),
    )
    return pl.pallas_call(
        _unpack_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(buffers.shape, buffers.dtype),
        input_output_aliases=UNPACK_ALIASES,
        interpret=_resolve(interpret),
    )(idx.astype(jnp.int32), msg, buffers)


# ------------------------------------------- fused unpack+pack (broadcast)


def _shuffle_kernel(recv_ref, send_ref, msg_ref, ro_ref, alias_ref,
                    outbuf_ref, outmsg_ref):
    r = pl.program_id(0)
    del alias_ref  # aliased with outbuf; untouched slots keep contents
    # unpack: the received message lands in this row's recv slot
    outbuf_ref[...] = msg_ref[...]
    # pack from the UPDATED buffer: when the next send slot is the slot
    # just written (the broadcast pipeline "forward what you received"),
    # the outgoing block is the message itself; otherwise it is the
    # DMA-selected old block.  No read-back of a freshly written block.
    same = recv_ref[r] == send_ref[r]
    outmsg_ref[...] = jnp.where(same, msg_ref[...], ro_ref[...])


def block_shuffle(buffers: jnp.ndarray, msg: jnp.ndarray,
                  recv_idx: jnp.ndarray, send_idx: jnp.ndarray,
                  *, interpret=None):
    """Fused unpack(t) + pack(t+1) for the broadcast family.

    buffers: [R, nslots, rows, lanes] (or flat [R, nslots, bs]); msg:
    one slot per row, received this round; recv_idx/send_idx: [R] int32
    slots.  Returns ``(new_buffers, out_msg)`` where
    ``new_buffers[r, recv_idx[r]] = msg[r]`` and ``out_msg[r] =
    new_buffers[r, send_idx[r]]`` (i.e. the pack sees the unpack's write
    -- the round-t+1 send of a round-t delivery).
    """
    if buffers.ndim == 3:
        return _on_flat(block_shuffle, buffers, (msg,), recv_idx, send_idx,
                        interpret=interpret)
    R, nslots, rows, lanes = buffers.shape
    tr, T = _grid_tiles(buffers)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(R, T),
        in_specs=[
            _row_spec(tr, lanes),
            # read-only buffer view: the send tile (pre-update content)
            _slot_spec(tr, lanes, _send_map),
            # aliased buffer: the recv tile (overwritten by the kernel)
            _slot_spec(tr, lanes, _recv_map),
        ],
        out_specs=[
            _slot_spec(tr, lanes, _recv_map),
            _row_spec(tr, lanes),
        ],
    )
    return pl.pallas_call(
        _shuffle_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(buffers.shape, buffers.dtype),
            jax.ShapeDtypeStruct((R, rows, lanes), buffers.dtype),
        ],
        input_output_aliases=SHUFFLE_ALIASES,
        interpret=_resolve(interpret),
    )(recv_idx.astype(jnp.int32), send_idx.astype(jnp.int32),
      msg, buffers, buffers)


# ---------------------------- staged shuffle (overlapped executor mode)


def _shuffle_staged_kernel(recv_ref, send_ref, msg_ref, pre_ref, alias_ref,
                           outbuf_ref, outmsg_ref):
    r = pl.program_id(0)
    del alias_ref  # aliased with outbuf; untouched slots keep contents
    # unpack: the received message lands in this row's recv slot
    outbuf_ref[...] = msg_ref[...]
    # the round-t+1 send block was packed from the PRE-update buffer
    # (``pre``) before the exchange completed; the unpack only changed
    # the recv slot, so the staged block is stale exactly when the next
    # send slot IS the recv slot -- patch that one case with the message.
    same = recv_ref[r] == send_ref[r]
    outmsg_ref[...] = jnp.where(same, msg_ref[...], pre_ref[...])


def block_shuffle_staged(buffers: jnp.ndarray, msg: jnp.ndarray,
                         pre: jnp.ndarray, recv_idx: jnp.ndarray,
                         send_idx: jnp.ndarray, *, interpret=None):
    """Overlap-staged variant of :func:`block_shuffle`.

    ``pre`` (one slot per row) is round t+1's send block packed from the
    buffer *before* round t's delivery landed, so it can be computed
    while the round-t exchange is still in flight.  The kernel writes
    ``msg`` into the recv slots and selects the outgoing message as
    ``msg`` where ``recv_idx == send_idx`` (the pipeline case -- the only
    slot the unpack changed) and ``pre`` everywhere else.  Bit-exact vs
    ``block_shuffle(buffers, msg, recv_idx, send_idx)`` whenever the
    schedule writes each slot at most once (the write-once invariant the
    static auditor proves).  Returns ``(new_buffers, out_msg)``.
    """
    if buffers.ndim == 3:
        return _on_flat(block_shuffle_staged, buffers, (msg, pre), recv_idx,
                        send_idx, interpret=interpret)
    R, nslots, rows, lanes = buffers.shape
    tr, T = _grid_tiles(buffers)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(R, T),
        in_specs=[
            _row_spec(tr, lanes),
            _row_spec(tr, lanes),
            # aliased buffer: the recv tile (overwritten by the kernel)
            _slot_spec(tr, lanes, _recv_map),
        ],
        out_specs=[
            _slot_spec(tr, lanes, _recv_map),
            _row_spec(tr, lanes),
        ],
    )
    return pl.pallas_call(
        _shuffle_staged_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(buffers.shape, buffers.dtype),
            jax.ShapeDtypeStruct((R, rows, lanes), buffers.dtype),
        ],
        input_output_aliases=SHUFFLE_STAGED_ALIASES,
        interpret=_resolve(interpret),
    )(recv_idx.astype(jnp.int32), send_idx.astype(jnp.int32),
      msg, pre, buffers)


# ------------------------------------- fused accumulate+capture (reduce)


def _acc_shuffle_kernel(acc_ref, fwd_ref, msg_ref, ro_ref, alias_ref,
                        outbuf_ref, outmsg_ref, scratch_ref, *, op, identity):
    r = pl.program_id(0)
    s = pl.program_id(2)
    # s == 0: accumulate the incoming partial into the acc slot.
    # s == 1: drain the (next round's) fwd slot to the identity.
    # The captured outgoing partial is staged through VMEM scratch at
    # s == 0, computed from pre-update values only (combined when the
    # fwd slot IS the acc slot, the old fwd block otherwise) -- never by
    # reading back a block written earlier in the grid, so interpret and
    # compiled modes agree bit-for-bit.
    combined = op_combine(op)(alias_ref[...], msg_ref[...])

    @pl.when(s == 0)
    def _():
        same = acc_ref[r] == fwd_ref[r]
        scratch_ref[...] = jnp.where(same, combined, ro_ref[...])

    ident = jnp.full_like(combined, identity)
    outbuf_ref[...] = jnp.where(s == 0, combined, ident)
    outmsg_ref[...] = scratch_ref[...]


def block_acc_shuffle(buffers: jnp.ndarray, msg: jnp.ndarray,
                      acc_idx: jnp.ndarray, fwd_idx: jnp.ndarray,
                      *, op: str = "sum", interpret=None):
    """Fused accumulate(t) + capture/drain(t+1) for the reduce family.

    buffers: [R, nslots, rows, lanes] (or flat [R, nslots, bs]); msg:
    one incoming partial per row; acc_idx/fwd_idx: [R] int32 slots.  Per
    row r, in order:

      1. ``buffers[r, acc_idx[r]] op= msg[r]``   (accumulate, round t)
      2. ``out_msg[r] = buffers[r, fwd_idx[r]]`` (capture, round t+1 --
         sees step 1's result when the slots coincide)
      3. ``buffers[r, fwd_idx[r]] = identity(op, dtype)``  (drain)

    ``op`` is ``"sum"`` (identity 0) or ``"max"`` (identity -inf /
    integer min).  Returns ``(new_buffers, out_msg)``.
    """
    if buffers.ndim == 3:
        return _on_flat(block_acc_shuffle, buffers, (msg,), acc_idx,
                        fwd_idx, op=op, interpret=interpret)
    R, nslots, rows, lanes = buffers.shape
    tr, T = _grid_tiles(buffers)
    identity = op_identity(op, buffers.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(R, T, 2),
        in_specs=[
            _row_spec(tr, lanes),
            # read-only buffer view: the fwd tile (pre-update content)
            _slot_spec(tr, lanes, _fwd_map),
            # aliased buffer: acc tile at s=0, fwd tile at s=1
            _slot_spec(tr, lanes, _step_map),
        ],
        out_specs=[
            _slot_spec(tr, lanes, _step_map),
            _row_spec(tr, lanes),
        ],
        scratch_shapes=[pltpu.VMEM((tr, lanes), buffers.dtype)],
    )
    kern = functools.partial(_acc_shuffle_kernel, op=op, identity=identity)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(buffers.shape, buffers.dtype),
            jax.ShapeDtypeStruct((R, rows, lanes), buffers.dtype),
        ],
        input_output_aliases=ACC_ALIASES,
        interpret=_resolve(interpret),
    )(acc_idx.astype(jnp.int32), fwd_idx.astype(jnp.int32),
      msg, buffers, buffers)


# ------------------- staged accumulate+capture (overlapped reduce mode)


def _acc_shuffle_staged_kernel(acc_ref, fwd_ref, msg_ref, pre_ref, alias_ref,
                               outbuf_ref, outmsg_ref, scratch_ref,
                               *, op, identity):
    r = pl.program_id(0)
    s = pl.program_id(2)
    # Same two-step grid as _acc_shuffle_kernel (s=0 accumulate, s=1
    # drain), but the captured outgoing partial for the non-coincident
    # case comes from ``pre`` -- the fwd block packed from the
    # PRE-update buffer while the exchange was in flight -- instead of a
    # second read-only buffer view.  The accumulate only changed the acc
    # slot, so ``pre`` is stale exactly when fwd == acc; patch that case
    # with the freshly combined value.
    combined = op_combine(op)(alias_ref[...], msg_ref[...])

    @pl.when(s == 0)
    def _():
        same = acc_ref[r] == fwd_ref[r]
        scratch_ref[...] = jnp.where(same, combined, pre_ref[...])

    ident = jnp.full_like(combined, identity)
    outbuf_ref[...] = jnp.where(s == 0, combined, ident)
    outmsg_ref[...] = scratch_ref[...]


def block_acc_shuffle_staged(buffers: jnp.ndarray, msg: jnp.ndarray,
                             pre: jnp.ndarray, acc_idx: jnp.ndarray,
                             fwd_idx: jnp.ndarray, *, op: str = "sum",
                             interpret=None):
    """Overlap-staged variant of :func:`block_acc_shuffle`.

    ``pre`` (one slot per row) is round t+1's fwd block packed from the
    buffer *before* round t's partial was accumulated, so it can be
    computed while the round-t exchange is still in flight.  Per row r:

      1. ``buffers[r, acc_idx[r]] op= msg[r]``   (accumulate, round t)
      2. ``out_msg[r]`` = the combined value where ``fwd_idx == acc_idx``
         (the only slot step 1 changed), ``pre[r]`` otherwise
      3. ``buffers[r, fwd_idx[r]] = identity(op, dtype)``  (drain)

    Bit-exact vs ``block_acc_shuffle(buffers, msg, acc_idx, fwd_idx)``:
    the sequential capture also reads pre-accumulate content everywhere
    except the coincident slot.  Returns ``(new_buffers, out_msg)``.
    """
    if buffers.ndim == 3:
        return _on_flat(block_acc_shuffle_staged, buffers, (msg, pre),
                        acc_idx, fwd_idx, op=op, interpret=interpret)
    R, nslots, rows, lanes = buffers.shape
    tr, T = _grid_tiles(buffers)
    identity = op_identity(op, buffers.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(R, T, 2),
        in_specs=[
            _row_spec(tr, lanes),
            _row_spec(tr, lanes),
            # aliased buffer: acc tile at s=0, fwd tile at s=1
            _slot_spec(tr, lanes, _step_map),
        ],
        out_specs=[
            _slot_spec(tr, lanes, _step_map),
            _row_spec(tr, lanes),
        ],
        scratch_shapes=[pltpu.VMEM((tr, lanes), buffers.dtype)],
    )
    kern = functools.partial(
        _acc_shuffle_staged_kernel, op=op, identity=identity)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(buffers.shape, buffers.dtype),
            jax.ShapeDtypeStruct((R, rows, lanes), buffers.dtype),
        ],
        input_output_aliases=ACC_STAGED_ALIASES,
        interpret=_resolve(interpret),
    )(acc_idx.astype(jnp.int32), fwd_idx.astype(jnp.int32),
      msg, pre, buffers)


# --------------------- fused dequantize+accumulate+requantize (reduce)


def _qacc_shuffle_kernel(acc_ref, fwd_ref, qmsg_ref, smsg_ref, ro_ref,
                         alias_ref, erro_ref, outbuf_ref, outerr_ref,
                         outq_ref, outs_ref, q_scr, s_scr, e_scr, *,
                         barrier):
    r = pl.program_id(0)
    s = pl.program_id(2)
    # Same two-step grid as _acc_shuffle_kernel (s=0 accumulate, s=1
    # drain), with the wire format quantized: one tile row is one
    # quantization block, the incoming message is its int8 lanes plus a
    # [tile_rows, 1] column of f32 scales, dequantized on the fly; the
    # captured outgoing partial is requantized for the next hop and its
    # requantization error accumulated into the matching err slot (the
    # per-hop term the error-feedback sum needs -- dropping it is a
    # first-order bias, see optim/compression.py).
    deq = dequant_blocks(qmsg_ref[...], smsg_ref[...], barrier=barrier)
    combined = alias_ref[...] + deq

    @pl.when(s == 0)
    def _():
        same = acc_ref[r] == fwd_ref[r]
        captured = jnp.where(same, combined, ro_ref[...])
        q, sc = quant_blocks(captured)
        q_scr[...] = q
        s_scr[...] = sc
        e_scr[...] = erro_ref[...] + quant_error(captured, q, sc,
                                                 barrier=barrier)

    outbuf_ref[...] = jnp.where(s == 0, combined, jnp.zeros_like(combined))
    outerr_ref[...] = e_scr[...]
    outq_ref[...] = q_scr[...]
    outs_ref[...] = s_scr[...]


def block_qacc_shuffle(buffers: jnp.ndarray, err: jnp.ndarray,
                       qmsg: jnp.ndarray, smsg: jnp.ndarray,
                       acc_idx: jnp.ndarray, fwd_idx: jnp.ndarray,
                       *, interpret=None):
    """Fused dequantize+accumulate(t) + requantize/capture/drain(t+1).

    The quantized-wire variant of :func:`block_acc_shuffle` (sum only).
    buffers/err: [R, nslots, nb, qb] f32 partial sums and their
    accumulated requantization errors, one quantization block per row
    (or flat [R, nslots, nb * qb]); qmsg: the int8 incoming payload in
    the same slot layout; smsg: [R, nb] f32 per-block scales.  Per row
    r, in order:

      1. ``buffers[r, acc_idx[r]] += dequant(qmsg[r], smsg[r])``
      2. capture ``buffers[r, fwd_idx[r]]`` (sees step 1 when the slots
         coincide), requantize it to ``(out_q[r], out_s[r])``
      3. ``err[r, fwd_idx[r]] += captured - dequant(out_q[r], out_s[r])``
      4. drain ``buffers[r, fwd_idx[r]]`` to zero

    Returns ``(new_buffers, new_err, out_q, out_s)``.  Quantization math
    is :mod:`repro.kernels.quant_ops`.  Compiled for the TPU, qb must be
    a multiple of 128 lanes (the default QBLOCK=256 is) and nb a
    multiple of :data:`QROWS` (:func:`slot_shape` pads it).
    """
    R, nslots = buffers.shape[:2]
    nb = smsg.shape[1]
    if buffers.ndim == 3:
        bs = buffers.shape[-1]
        assert bs % nb == 0, (bs, nb)
        shape = slot_shape(bs, jnp.float32, qblock=bs // nb)
        pad = shape[0] - nb
        nbuf, nerr, q, s = block_qacc_shuffle(
            _to_tiles(buffers, shape), _to_tiles(err, shape),
            _to_tiles(qmsg, shape), jnp.pad(smsg, ((0, 0), (0, pad))),
            acc_idx, fwd_idx, interpret=interpret)
        return (_from_tiles(nbuf, bs), _from_tiles(nerr, bs),
                _from_tiles(q, bs), s[:, :nb])
    qb = buffers.shape[-1]
    assert buffers.shape[2] == nb, (buffers.shape, nb)
    interpret = _resolve(interpret)
    tq, T = _grid_tiles(buffers, unit=QROWS)
    sspec = _row_spec(tq, 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(R, T, 2),
        in_specs=[
            _row_spec(tq, qb),
            sspec,
            # read-only buffer view: the fwd tile (pre-update content)
            _slot_spec(tq, qb, _fwd_map),
            # aliased buffer: acc tile at s=0, fwd tile at s=1
            _slot_spec(tq, qb, _step_map),
            # aliased err buffer: always the fwd tile
            _slot_spec(tq, qb, _fwd_map),
        ],
        out_specs=[
            _slot_spec(tq, qb, _step_map),
            _slot_spec(tq, qb, _fwd_map),
            _row_spec(tq, qb),
            sspec,
        ],
        scratch_shapes=[
            pltpu.VMEM((tq, qb), jnp.int8),
            pltpu.VMEM((tq, 1), jnp.float32),
            pltpu.VMEM((tq, qb), jnp.float32),
        ],
    )
    # The optimization barrier pins round-after-multiply semantics for
    # the interpreter's XLA; Mosaic has no such primitive and never
    # contracts the dequantize into the accumulate.
    kern = functools.partial(_qacc_shuffle_kernel, barrier=interpret)
    nbuf, nerr, q, s = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(buffers.shape, jnp.float32),
            jax.ShapeDtypeStruct(buffers.shape, jnp.float32),
            jax.ShapeDtypeStruct((R, nb, qb), jnp.int8),
            jax.ShapeDtypeStruct((R, nb, 1), jnp.float32),
        ],
        # operands counted including the 2 prefetch scalars:
        # 5 = 2nd buffer operand -> new_buffers, 6 = err -> new_err
        input_output_aliases=QACC_ALIASES,
        interpret=interpret,
    )(acc_idx.astype(jnp.int32), fwd_idx.astype(jnp.int32),
      qmsg, smsg.reshape(R, nb, 1), buffers, buffers, err)
    return nbuf, nerr, q, s.reshape(R, nb)


# ------------------------------------------------------- audit registry
#
# Machine-checkable metadata for repro.analysis.kernelaudit: for every
# kernel, the grid, the operand layout (the SAME index-map function
# objects and alias dicts the pallas_call above was built with), which
# logical storage each operand addresses, and at which grid points an
# input block's value is actually consumed ("live").  The detector
# replays the grid in Pallas' sequential lexicographic order and flags
# (a) two grid points writing one block of one storage outside the
# declared drain dimension, (b) a live input read of a block a strictly
# earlier grid point wrote (the interpret==compiled divergence hazard),
# and (c) alias pairs whose index maps disagree anywhere on the grid.


@dataclass(frozen=True)
class OperandAudit:
    """One pallas operand as the race detector sees it.

    ``storage`` names the logical HBM buffer the index map addresses --
    operands passed the same array (the read-only + aliased buffer
    trick) share a storage name, as does an output aliased onto an
    input.  ``live`` is None for "consumed at every grid point" or a
    predicate over the grid tuple; a fetched-but-discarded block (the
    drain sub-round's alias read) is dead and cannot race.
    """

    name: str
    storage: str
    index_map: Callable
    block: Tuple[int, ...]
    live: Optional[Callable] = None


@dataclass(frozen=True)
class KernelAudit:
    """Static audit description of one schedule-driven kernel."""

    name: str
    grid: Tuple[int, ...]
    num_scalar_prefetch: int
    scalar_names: Tuple[str, ...]
    inputs: Tuple[OperandAudit, ...]
    outputs: Tuple[OperandAudit, ...]
    #: pallas input_output_aliases (operand-indexed incl. prefetch)
    aliases: Tuple[Tuple[int, int], ...]
    #: grid dims along which one block may be rewritten sequentially
    #: (the two-step accumulate-then-drain sub-round); () elsewhere
    drain_dims: Tuple[int, ...]
    #: buffer dtype -> expected output dtypes (the no-silent-widening
    #: contract of the sum/max/qacc paths)
    out_dtypes: Callable


KERNEL_NAMES = ("block_pack", "block_unpack", "block_shuffle",
                "block_shuffle_staged", "block_acc_shuffle",
                "block_acc_shuffle_staged", "block_qacc_shuffle")


def _live_acc_step(g) -> bool:
    """Accumulate/drain kernels consume their inputs only in the s == 0
    sub-round; every s == 1 fetch is staged-through or discarded."""
    return g[2] == 0


def kernel_audit_spec(name: str, *, R: int, nslots: int, bs: int,
                      nb: int = 1, dtype=jnp.float32) -> KernelAudit:
    """The :class:`KernelAudit` for kernel ``name`` at concrete sizes:
    ``R`` rows of ``nslots`` slots of ``bs`` elements of ``dtype``
    (``nb`` quantization blocks per slot for ``block_qacc_shuffle``,
    whose buffers are float32).

    Single-sourced with the real calls: the returned records reference
    the very index-map functions and alias dicts the ``pallas_call``\\ s
    in this module pass, and the grid comes from the same
    :func:`slot_shape` / :func:`row_tile` layout, so auditing them
    audits the shipped kernels.
    """
    f32, i8 = jnp.float32, jnp.int8
    if name == "block_qacc_shuffle":
        rows, lanes = slot_shape(bs, f32, qblock=bs // nb)
        tr = row_tile(rows, lanes, f32, QROWS)
    else:
        rows, lanes = slot_shape(bs, dtype)
        tr = row_tile(rows, lanes, dtype)
    T = rows // tr
    slot, row = (1, 1, tr, lanes), (1, tr, lanes)
    if name == "block_pack":
        return KernelAudit(
            name=name, grid=(R, T), num_scalar_prefetch=1,
            scalar_names=("idx",),
            inputs=(OperandAudit("buffers", "buf", _slot_map1, slot),),
            outputs=(OperandAudit("out", "msg", _row_map, row),),
            aliases=(), drain_dims=(),
            out_dtypes=lambda dt: (dt,))
    if name == "block_unpack":
        return KernelAudit(
            name=name, grid=(R, T), num_scalar_prefetch=1,
            scalar_names=("idx",),
            inputs=(
                OperandAudit("msg", "msg", _row_map, row),
                # aliased with the output; its fetched block is never
                # consumed (the kernel dels the ref)
                OperandAudit("buffers", "buf", _slot_map1, slot,
                             live=lambda g: False),
            ),
            outputs=(OperandAudit("out", "buf", _slot_map1, slot),),
            aliases=tuple(sorted(UNPACK_ALIASES.items())), drain_dims=(),
            out_dtypes=lambda dt: (dt,))
    if name == "block_shuffle":
        return KernelAudit(
            name=name, grid=(R, T), num_scalar_prefetch=2,
            scalar_names=("recv_idx", "send_idx"),
            inputs=(
                OperandAudit("msg", "msg", _row_map, row),
                OperandAudit("ro", "buf", _send_map, slot),
                OperandAudit("alias", "buf", _recv_map, slot,
                             live=lambda g: False),
            ),
            outputs=(
                OperandAudit("outbuf", "buf", _recv_map, slot),
                OperandAudit("outmsg", "outmsg", _row_map, row),
            ),
            aliases=tuple(sorted(SHUFFLE_ALIASES.items())), drain_dims=(),
            out_dtypes=lambda dt: (dt, dt))
    if name == "block_shuffle_staged":
        return KernelAudit(
            name=name, grid=(R, T), num_scalar_prefetch=2,
            scalar_names=("recv_idx", "send_idx"),
            inputs=(
                OperandAudit("msg", "msg", _row_map, row),
                OperandAudit("pre", "pre", _row_map, row),
                OperandAudit("alias", "buf", _recv_map, slot,
                             live=lambda g: False),
            ),
            outputs=(
                OperandAudit("outbuf", "buf", _recv_map, slot),
                OperandAudit("outmsg", "outmsg", _row_map, row),
            ),
            aliases=tuple(sorted(SHUFFLE_STAGED_ALIASES.items())),
            drain_dims=(),
            out_dtypes=lambda dt: (dt, dt))
    if name == "block_acc_shuffle":
        return KernelAudit(
            name=name, grid=(R, T, 2), num_scalar_prefetch=2,
            scalar_names=("acc_idx", "fwd_idx"),
            inputs=(
                OperandAudit("msg", "msg", _row_map, row,
                             live=_live_acc_step),
                OperandAudit("ro", "buf", _fwd_map, slot,
                             live=_live_acc_step),
                OperandAudit("alias", "buf", _step_map, slot,
                             live=_live_acc_step),
            ),
            outputs=(
                OperandAudit("outbuf", "buf", _step_map, slot),
                OperandAudit("outmsg", "outmsg", _row_map, row),
            ),
            aliases=tuple(sorted(ACC_ALIASES.items())), drain_dims=(2,),
            out_dtypes=lambda dt: (dt, dt))
    if name == "block_acc_shuffle_staged":
        return KernelAudit(
            name=name, grid=(R, T, 2), num_scalar_prefetch=2,
            scalar_names=("acc_idx", "fwd_idx"),
            inputs=(
                OperandAudit("msg", "msg", _row_map, row,
                             live=_live_acc_step),
                OperandAudit("pre", "pre", _row_map, row,
                             live=_live_acc_step),
                OperandAudit("alias", "buf", _step_map, slot,
                             live=_live_acc_step),
            ),
            outputs=(
                OperandAudit("outbuf", "buf", _step_map, slot),
                OperandAudit("outmsg", "outmsg", _row_map, row),
            ),
            aliases=tuple(sorted(ACC_STAGED_ALIASES.items())),
            drain_dims=(2,),
            out_dtypes=lambda dt: (dt, dt))
    if name == "block_qacc_shuffle":
        scale = (1, tr, 1)
        return KernelAudit(
            name=name, grid=(R, T, 2), num_scalar_prefetch=2,
            scalar_names=("acc_idx", "fwd_idx"),
            inputs=(
                OperandAudit("qmsg", "qmsg", _row_map, row,
                             live=_live_acc_step),
                OperandAudit("smsg", "smsg", _row_map, scale,
                             live=_live_acc_step),
                OperandAudit("ro", "buf", _fwd_map, slot,
                             live=_live_acc_step),
                OperandAudit("alias", "buf", _step_map, slot,
                             live=_live_acc_step),
                OperandAudit("erro", "err", _fwd_map, slot,
                             live=_live_acc_step),
            ),
            outputs=(
                OperandAudit("outbuf", "buf", _step_map, slot),
                OperandAudit("outerr", "err", _fwd_map, slot),
                OperandAudit("outq", "outq", _row_map, row),
                OperandAudit("outs", "outs", _row_map, scale),
            ),
            aliases=tuple(sorted(QACC_ALIASES.items())), drain_dims=(2,),
            out_dtypes=lambda dt: (f32, f32, i8, f32))
    raise ValueError(f"unknown kernel {name!r} (use one of {KERNEL_NAMES})")
