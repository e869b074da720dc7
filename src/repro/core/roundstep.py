"""Pluggable per-round data plane for the collective family.

The paper deliberately separates the O(log p) *schedule computation*
from the per-round *data movement*, and the whole collective family
(broadcast / all-broadcast / reduction / all-reduction, arXiv:2407.18004)
shares one per-round inner step on its block buffers:

  * broadcast family: ``pack`` one block per row into the outgoing
    message -> exchange -> ``unpack`` into one slot per row;
  * reduce family: capture the forwarded partial and drain its slot ->
    exchange -> ``accumulate`` the incoming partial (sum/max).

:class:`RoundStep` is that step as a small backend interface.  Buffers
are ``[R, nslots, *slot]`` arrays (R rows: one per rank in the batched
simulator data plane, one per root in the all-gather family, a single
row inside a per-rank ``shard_map`` body), with the slot layout each
backend asks for through :meth:`RoundStep.slot_shape`; slot vectors are
``[R]`` int32 columns of the engine's per-round tables
(:meth:`ScheduleBundle.per_round_tables` /
:meth:`ScheduleBundle.reversed_per_round_tables`).

Slot layout.  Pallas always lays a slot out as a tile stack
(:func:`repro.kernels.layout.slot_shape`): ``(rows, 128)`` for a plain
slot, ``(rows, qblock)`` with one quantization block a row for a
quantized (``qblock``) slot; its kernels need that to compile.  The jnp
backend takes the same tile stack, for both kinds of slot, whenever it
pads the slot by at most ``1/TILE_PAD`` of its elements, and keeps the
flat layout (``(bs,)``, or whole qblocks) otherwise: small slots, whose
tile padding would inflate the wire, stay flat.  The reason is the
TPU's memory layout: an array's last two dimensions are stored in
(8, 128) tiles (16 or 32 rows for narrower dtypes), so in a flat
``[R, nslots, bs]`` buffer the slot index is a tile row, and a slot
write or read touches one sublane of every tile of the buffer.  In a
tile stack the slot index lies outside the tile, so each round writes
and reads whole tiles of one slot, and a quantized slot's
``[R * rows, qblock]`` view for the block quantizer is a bitcast.  Only
the layout differs: the values, their accumulation order and the zero
padding are the same (a pad row of a quantized slot is an all-zero
block, whose scale floors and which dequantizes to exact zeros).

Two backends implement it:

  * ``"jnp"`` -- the pure-jnp reference (:mod:`repro.kernels.ref`):
    gathers and ``.at[]`` scatters; lowers everywhere, used by default;
  * ``"pallas"`` -- the fused Pallas kernels
    (:mod:`repro.kernels.block_pack`): scalar-prefetched schedule
    columns drive BlockSpec index maps, so block selection is pure DMA
    index mapping; compiled on TPU, ``interpret=True`` elsewhere.

Both backends implement identical update order (unpack-then-pack;
accumulate-then-capture-then-drain), so they agree **bit-exactly** --
asserted by the simulator certification harness
(:func:`dataplane_broadcast` / :func:`dataplane_reduce` /
:func:`dataplane_allgather`, wired into ``simulate_*(backend=...)``)
and by the backend-parametrized collective tests.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

from repro.kernels import layout

from . import tracing
from .tracing import scope

__all__ = [
    "RoundStep",
    "JnpRoundStep",
    "PallasRoundStep",
    "get_round_step",
    "clamp_slots",
    "broadcast_slot_plan",
    "reduce_slot_plan",
    "scatter_slot_plan",
    "PhaseStatic",
    "broadcast_phase_static",
    "allgather_phase_static",
    "reduce_phase_static",
    "scatter_phase_static",
    "dataplane_broadcast",
    "dataplane_allgather",
    "dataplane_reduce",
    "dataplane_hier_broadcast",
    "dataplane_hier_reduce",
    "dataplane_hier_allreduce",
    "dataplane_hier_allgather",
]

BACKENDS = ("jnp", "pallas")

#: A jnp slot takes the tile stack when the stack pads it by at most
#: ``1/TILE_PAD`` of its elements, and stays flat otherwise.
TILE_PAD = 32


# ------------------------------------------------------------ slot plans
#
# Slot plans are cached process-wide in the engine's spec-keyed plan
# cache (keyed on (p, root, n) -- bundles are themselves cached, so the
# bundle identity is implied by the key).  The returned arrays are
# immutable and shared: a CollectivePlan holds them for its lifetime,
# and repeated per-call lowering (the legacy circulant_* path) pays the
# clamping exactly once per process.


def clamp_slots(eff: np.ndarray, n: int, garbage: Optional[int] = None) -> np.ndarray:
    """Effective block indices -> buffer slots: negative ("idle this
    round") entries address the garbage slot, entries > n-1 are capped
    to n-1 (final-phase re-sends), exactly as in Algorithm 1."""
    g = n if garbage is None else garbage
    return np.where(eff < 0, g, np.minimum(eff, n - 1)).astype(np.int32)


def _frozen(*arrays: np.ndarray) -> Tuple[np.ndarray, ...]:
    for a in arrays:
        a.setflags(write=False)
    return arrays


def broadcast_slot_plan(bundle, n: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(recv_slots, send_slots, ks): clamped [R, p] forward slot tables.

    Row t is the slot column of forward round t; buffers carry ``n+1``
    slots with slot ``n`` the garbage slot (Correctness Condition 1
    guarantees sender and receiver address garbage in the same rounds).
    Cached process-wide; the returned arrays are immutable and shared.
    """
    from .engine import cached_plan

    def build():
        recv_eff, send_eff, ks = bundle.per_round_tables(n)
        return _frozen(clamp_slots(recv_eff, n), clamp_slots(send_eff, n), ks)

    return cached_plan(("slots/bcast", bundle.p, bundle.root, int(n)), build)


def reduce_slot_plan(bundle, n: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(fwd_slots, acc_slots, ks): clamped [R, p] reversed slot tables.

    Buffers carry ``n+2`` slots: slot ``n`` is garbage, slot ``n+1``
    holds the op identity and is never overwritten with data.  The root
    never forwards a partial (forward rounds never send TO the root, so
    reversed rounds never send FROM it) -- its fwd column is pinned to
    the identity slot, so capped final-phase entries ship the identity
    instead of a live partial.  Cached process-wide; immutable arrays.
    """
    from .engine import cached_plan

    def build():
        fwd_eff, acc_eff, ks = bundle.reversed_per_round_tables(n)
        fwd = clamp_slots(fwd_eff, n)
        fwd[:, bundle.root] = n + 1
        return _frozen(fwd, clamp_slots(acc_eff, n), ks)

    return cached_plan(("slots/reduce", bundle.p, bundle.root, int(n)), build)


def scatter_slot_plan(bundle, n: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(fwd_slots, acc_slots, ks): clamped reversed tables *without* the
    root identity-slot pinning -- the reduce-scatter form, where capped
    final-phase entries are real deliveries routed by drain-after-send
    (buffers carry ``n+1`` slots, slot ``n`` garbage).  Cached."""
    from .engine import cached_plan

    def build():
        fwd_eff, acc_eff, ks = bundle.reversed_per_round_tables(n)
        return _frozen(clamp_slots(fwd_eff, n), clamp_slots(acc_eff, n), ks)

    return cached_plan(("slots/scatter", bundle.p, bundle.root, int(n)), build)


# ------------------------------------------------------- phase statics
#
# A PhaseStatic is the auditable description of one schedule phase: the
# exact clamped slot tables a plan's executor closed over (the cached
# arrays themselves, by identity), the skip-column sequence and the
# per-round wire rotations.  Plans of every flavour (device
# CollectivePlan / HierPlan, host HostDataPlan / HierHostPlan) expose a
# ``statics`` tuple of these, which repro.analysis.planaudit checks
# against the bundle and the closed-form round counts without running a
# single round.


@dataclass(frozen=True, eq=False)
class PhaseStatic:
    """Static per-phase audit record (see :mod:`repro.analysis`).

    ``kind`` is the phase family (``"broadcast"``, ``"allgather"``,
    ``"reduce"``, ``"scatter"``); ``direction`` is ``"fwd"`` for
    broadcast-direction phases and ``"rev"`` for reversed (reduction)
    phases.  ``slots`` holds the clamped [R, p] tables in execution
    order -- ``(recv, send)`` forward, ``(fwd, acc)`` reversed,
    ``(recv,)`` for the allgather family -- and ``shifts[t]`` is the
    signed-free rotation applied on the wire in round t (rank r sends to
    ``(r + shifts[t]) % p``).  ``nslots`` is the buffer slot count the
    tables address (n+1, or n+2 for the identity-pinned reduce layout).
    """

    kind: str
    direction: str
    p: int
    root: int
    n: int
    nslots: int
    slots: Tuple[np.ndarray, ...]
    ks: np.ndarray
    shifts: Tuple[int, ...]
    axis: Optional[str] = None
    #: True when the executor runs the overlapped (double-buffered) round
    #: loop: round t+1's block is packed from the pre-update buffer while
    #: round t's exchange is in flight, then patched by the staged step.
    #: The auditor additionally proves the staleness condition on these.
    overlap: bool = False


def broadcast_phase_static(bundle, n: int, axis: Optional[str] = None,
                           overlap: bool = False) -> PhaseStatic:
    """Audit record of a forward broadcast phase (cached tables shared)."""
    recv, send, ks = broadcast_slot_plan(bundle, n)
    shifts = tuple(int(bundle.skip[int(k)]) for k in ks)
    return PhaseStatic(kind="broadcast", direction="fwd", p=bundle.p,
                       root=bundle.root, n=int(n), nslots=int(n) + 1,
                       slots=(recv, send), ks=ks, shifts=shifts, axis=axis,
                       overlap=overlap)


def allgather_phase_static(bundle, n: int, axis: Optional[str] = None,
                           overlap: bool = False) -> PhaseStatic:
    """Audit record of an all-to-all broadcast phase: only the receive
    table is static per rank (send slots are derived per root row via
    Condition 2's base rotation at run time)."""
    recv, _send, ks = broadcast_slot_plan(bundle, n)
    shifts = tuple(int(bundle.skip[int(k)]) for k in ks)
    return PhaseStatic(kind="allgather", direction="fwd", p=bundle.p,
                       root=bundle.root, n=int(n), nslots=int(n) + 1,
                       slots=(recv,), ks=ks, shifts=shifts, axis=axis,
                       overlap=overlap)


def reduce_phase_static(bundle, n: int, axis: Optional[str] = None,
                        overlap: bool = False) -> PhaseStatic:
    """Audit record of a reversed reduction phase (identity-pinned root
    column, n+2-slot layout; partials travel against the skips)."""
    fwd, acc, ks = reduce_slot_plan(bundle, n)
    shifts = tuple((bundle.p - int(bundle.skip[int(k)])) % bundle.p
                   for k in ks)
    return PhaseStatic(kind="reduce", direction="rev", p=bundle.p,
                       root=bundle.root, n=int(n), nslots=int(n) + 2,
                       slots=(fwd, acc), ks=ks, shifts=shifts, axis=axis,
                       overlap=overlap)


def scatter_phase_static(bundle, n: int, axis: Optional[str] = None,
                         overlap: bool = False) -> PhaseStatic:
    """Audit record of a reduce-scatter phase (unpinned reversed tables,
    n+1-slot layout with drain-after-send routing)."""
    fwd, acc, ks = scatter_slot_plan(bundle, n)
    shifts = tuple((bundle.p - int(bundle.skip[int(k)])) % bundle.p
                   for k in ks)
    return PhaseStatic(kind="scatter", direction="rev", p=bundle.p,
                       root=bundle.root, n=int(n), nslots=int(n) + 1,
                       slots=(fwd, acc), ks=ks, shifts=shifts, axis=axis,
                       overlap=overlap)


# ------------------------------------------------------------- interface


class RoundStep:
    """One collective round's data movement on [R, nslots, bs] buffers.

    ``pack``/``unpack`` are the plain first/last-round primitives;
    ``shuffle`` fuses unpack(t) + pack(t+1) for the broadcast family and
    ``acc_shuffle`` fuses accumulate(t) + capture/drain(t+1) for the
    reduce family -- one backend call per steady-state round.
    """

    backend: str

    def slot_shape(self, bs: int, dtype,
                   qblock: Optional[int] = None) -> Tuple[int, ...]:
        """Array shape of one buffer slot holding ``bs`` elements of
        ``dtype`` (zero padded as the backend needs; quantized-wire
        slots hold whole ``qblock``-element quantization blocks).
        Raises ``ValueError`` for a slot this backend cannot run."""
        raise NotImplementedError

    def pack(self, buf, idx):
        """[R, S, *slot], [R] -> [R, *slot]: out[r] = buf[r, idx[r]]."""
        raise NotImplementedError

    def unpack(self, buf, msg, idx):
        """buf[r, idx[r]] = msg[r]; untouched slots keep contents."""
        raise NotImplementedError

    def shuffle(self, buf, msg, recv_idx, send_idx):
        """Fused unpack+pack -> (new_buf, out_msg); the pack reads the
        *updated* buffer (pipeline: forward next what was just received)."""
        raise NotImplementedError

    def shuffle_staged(self, buf, msg, pre, recv_idx, send_idx):
        """Overlap-staged shuffle -> (new_buf, out_msg): ``pre`` is the
        next send block packed from the PRE-update buffer (computable
        while the exchange is in flight); the step writes msg into the
        recv slots and patches the one stale case recv == send.
        Bit-exact vs :meth:`shuffle` under the write-once invariant."""
        raise NotImplementedError

    def acc_shuffle(self, buf, msg, acc_idx, fwd_idx, *, op: str = "sum"):
        """Fused accumulate+capture/drain -> (new_buf, out_msg):
        buf[acc] op= msg, then out = buf[fwd] (post-accumulate when the
        slots coincide), then buf[fwd] = identity(op, dtype)."""
        raise NotImplementedError

    def acc_shuffle_staged(self, buf, msg, pre, acc_idx, fwd_idx, *,
                           op: str = "sum"):
        """Overlap-staged acc_shuffle -> (new_buf, out_msg): ``pre`` is
        the next fwd block packed from the PRE-accumulate buffer; the
        step accumulates, patches the coincident fwd == acc case with
        the combined value, and drains.  Bit-exact vs
        :meth:`acc_shuffle`."""
        raise NotImplementedError

    def qacc_shuffle(self, buf, err, qmsg, smsg, acc_idx, fwd_idx):
        """Quantized-wire acc_shuffle (sum only) -> (new_buf, new_err,
        out_q, out_s): dequantize (qmsg, smsg) and accumulate into
        buf[acc], requantize the captured buf[fwd] for the wire,
        accumulate its requantization error into err[fwd], drain
        buf[fwd] to zero."""
        raise NotImplementedError


class JnpRoundStep(RoundStep):
    """Pure-jnp reference backend (gathers + ``.at[]`` scatters).

    Methods go through process-cached ``jax.jit`` wrappers, so eager
    host-side use (the simulator data plane) amortizes tracing across
    the sweep; inside an enclosing jit/shard_map trace they inline.
    """

    backend = "jnp"

    def slot_shape(self, bs, dtype, qblock=None):
        tiles = layout.slot_shape(bs, dtype, qblock)
        # a row narrower than whole lanes is stored padded to them
        stored = tiles[0] * -(-tiles[1] // layout.LANES) * layout.LANES
        if TILE_PAD * (stored - bs) <= bs:
            return tiles
        whole = qblock or 1
        return (int(-(-bs // whole) * whole),)

    def pack(self, buf, idx):
        with scope(tracing.RS_PACK):
            return _jnp_call("block_pack_ref", buf, idx)

    def unpack(self, buf, msg, idx):
        with scope(tracing.RS_UNPACK):
            return _jnp_call("block_unpack_ref", buf, msg, idx)

    def shuffle(self, buf, msg, recv_idx, send_idx):
        with scope(tracing.RS_SHUFFLE):
            return _jnp_call("block_shuffle_ref", buf, msg, recv_idx,
                             send_idx)

    def shuffle_staged(self, buf, msg, pre, recv_idx, send_idx):
        with scope(tracing.RS_SHUFFLE_STAGED):
            return _jnp_call("block_shuffle_staged_ref", buf, msg, pre,
                             recv_idx, send_idx)

    def acc_shuffle(self, buf, msg, acc_idx, fwd_idx, *, op: str = "sum"):
        with scope(tracing.RS_ACC_SHUFFLE):
            return _jnp_call("block_acc_shuffle_ref", buf, msg, acc_idx,
                             fwd_idx, op=op)

    def acc_shuffle_staged(self, buf, msg, pre, acc_idx, fwd_idx, *,
                           op: str = "sum"):
        with scope(tracing.RS_ACC_SHUFFLE_STAGED):
            return _jnp_call("block_acc_shuffle_staged_ref", buf, msg, pre,
                             acc_idx, fwd_idx, op=op)

    def qacc_shuffle(self, buf, err, qmsg, smsg, acc_idx, fwd_idx):
        with scope(tracing.RS_QACC_SHUFFLE):
            return _jnp_call("block_qacc_shuffle_ref", buf, err, qmsg, smsg,
                             acc_idx, fwd_idx)


_jnp_jits = {}


def _jnp_call(name, *args, **static):
    key = (name, tuple(sorted(static.items())))
    if key not in _jnp_jits:
        import functools

        import jax

        from repro.kernels import ref

        fn = getattr(ref, name)
        _jnp_jits[key] = jax.jit(functools.partial(fn, **static) if static
                                 else fn)
    return _jnp_jits[key](*args)


class PallasRoundStep(RoundStep):
    """Pallas fast path: scalar-prefetched schedule columns select the
    HBM blocks to DMA.  ``interpret=None`` auto-detects the platform
    (compiled on TPU, interpret-mode on CPU CI).  Calls route through
    the jit'd :mod:`repro.kernels.ops` wrappers, so eager host-side use
    hits the compile cache.  Slots are ``(rows, lanes)`` tile stacks
    (:func:`repro.kernels.block_pack.slot_shape`)."""

    backend = "pallas"

    def __init__(self, interpret: Optional[bool] = None):
        from repro.kernels.ops import resolve_interpret

        self.interpret = resolve_interpret(interpret)

    def slot_shape(self, bs, dtype, qblock=None):
        from repro.kernels.block_pack import LANES, slot_shape, tileable

        if not self.interpret:
            if not tileable(dtype):
                raise ValueError(
                    f"{np.dtype(dtype).name} slots cannot be tiled for the "
                    "compiled Pallas round step (use 8/16/32-bit payloads)")
            if qblock is not None and qblock % LANES:
                raise ValueError(
                    f"qblock={qblock} is not a multiple of {LANES} lanes; "
                    "the compiled quantized round step cannot tile it")
        return slot_shape(bs, dtype, qblock)

    def pack(self, buf, idx):
        from repro.kernels.ops import schedule_pack

        with scope(tracing.RS_PACK):
            return schedule_pack(buf, idx, interpret=self.interpret)

    def unpack(self, buf, msg, idx):
        from repro.kernels.ops import schedule_unpack

        with scope(tracing.RS_UNPACK):
            return schedule_unpack(buf, msg, idx, interpret=self.interpret)

    def shuffle(self, buf, msg, recv_idx, send_idx):
        from repro.kernels.ops import schedule_shuffle

        with scope(tracing.RS_SHUFFLE):
            return schedule_shuffle(buf, msg, recv_idx, send_idx,
                                    interpret=self.interpret)

    def shuffle_staged(self, buf, msg, pre, recv_idx, send_idx):
        from repro.kernels.ops import schedule_shuffle_staged

        with scope(tracing.RS_SHUFFLE_STAGED):
            return schedule_shuffle_staged(buf, msg, pre, recv_idx, send_idx,
                                           interpret=self.interpret)

    def acc_shuffle(self, buf, msg, acc_idx, fwd_idx, *, op: str = "sum"):
        from repro.kernels.ops import schedule_acc_shuffle

        with scope(tracing.RS_ACC_SHUFFLE):
            return schedule_acc_shuffle(buf, msg, acc_idx, fwd_idx, op=op,
                                        interpret=self.interpret)

    def acc_shuffle_staged(self, buf, msg, pre, acc_idx, fwd_idx, *,
                           op: str = "sum"):
        from repro.kernels.ops import schedule_acc_shuffle_staged

        with scope(tracing.RS_ACC_SHUFFLE_STAGED):
            return schedule_acc_shuffle_staged(buf, msg, pre, acc_idx,
                                               fwd_idx, op=op,
                                               interpret=self.interpret)

    def qacc_shuffle(self, buf, err, qmsg, smsg, acc_idx, fwd_idx):
        from repro.kernels.ops import schedule_qacc_shuffle

        with scope(tracing.RS_QACC_SHUFFLE):
            return schedule_qacc_shuffle(buf, err, qmsg, smsg, acc_idx,
                                         fwd_idx, interpret=self.interpret)


_step_handles = {}


def get_round_step(backend: str = "jnp",
                   interpret: Optional[bool] = None) -> RoundStep:
    """Round-step backend factory: ``"jnp"`` (portable reference) or
    ``"pallas"`` (fused kernels; ``interpret`` as in
    :func:`repro.kernels.ops.resolve_interpret`).

    Handles are stateless and cached per ``(backend, interpret)``, so a
    plan (repro.core.comm) owns the same shared step instance its
    sibling plans use -- no per-call construction or platform sniffing.
    """
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown round-step backend {backend!r} (use one of {BACKENDS})"
        )
    key = (backend, interpret)
    step = _step_handles.get(key)
    if step is None:
        step = (JnpRoundStep() if backend == "jnp"
                else PallasRoundStep(interpret))
        _step_handles[key] = step
    return step


# --------------------------------------------- host data-plane executors
#
# Single-process executions of the full collectives with the R rows of
# the batched kernels standing in for the p ranks and the network
# exchange realized as a row rotation (ppermute's rotation r -> (r+s)%p
# is exactly jnp.roll along the rank axis).  The simulator runs these
# next to its message-passing reference and asserts bit-exact agreement
# -- the certification path for the Pallas backend on CPU CI.
#
# The executors live on the cached host plans of :mod:`repro.core.comm`
# (slot tables + step handle precomputed once per (kind, p, n, root,
# op, backend)); these wrappers keep the original one-shot entry points.


def dataplane_broadcast(p: int, n: int, root: int, values: np.ndarray,
                        backend: str,
                        interpret: Optional[bool] = None) -> np.ndarray:
    """Execute the n-block broadcast data plane on host arrays.

    ``values``: [n] (or [n, bs]) block payloads at the root.  Returns
    the final [p, n, bs] data slots of every rank.
    """
    from .comm import host_plan

    return host_plan("broadcast", p, n, root=root, backend=backend,
                     interpret=interpret).run(values)


def dataplane_allgather(p: int, n: int, values: np.ndarray, backend: str,
                        interpret: Optional[bool] = None) -> np.ndarray:
    """Execute the all-to-all broadcast data plane on host arrays.

    ``values``: [p, n] (or [p, n, bs]) -- root j's block payloads.  The
    [p_rank, p_root] buffer grid is flattened rank-major onto the kernel
    rows, so the exchange is a roll by ``skip * p`` flat rows.  Returns
    the final [p_rank, p_root, n, bs] data slots.
    """
    from .comm import host_plan

    return host_plan("allgather", p, n, backend=backend,
                     interpret=interpret).run(values)


def dataplane_reduce(p: int, n: int, root: int, values: np.ndarray, op: str,
                     backend: str,
                     interpret: Optional[bool] = None) -> np.ndarray:
    """Execute the reversed-schedule reduction data plane on host arrays.

    ``values``: [p, n] (or [p, n, bs]) per-rank block contributions.
    Returns the final [p, n, bs] data slots (row ``root`` holds the
    op-reduction; other rows are drained to the identity).
    """
    from .comm import host_plan

    return host_plan("reduce", p, n, root=root, op=op, backend=backend,
                     interpret=interpret).run(values)


# The hierarchical (two-level) variants compose the flat host plans per
# level (repro.core.hier.hier_host_plan); these wrappers keep the
# one-shot entry-point shape of their flat siblings above.


def dataplane_hier_broadcast(nodes: int, cores: int, n_inter: int,
                             n_intra: int, root: int, values: np.ndarray,
                             backend: str,
                             interpret: Optional[bool] = None) -> np.ndarray:
    """Two-level broadcast data plane: flat [m] payload at the flat
    node-major ``root`` -> final [nodes, cores, m] state of every rank."""
    from .hier import hier_host_plan

    return hier_host_plan("broadcast", nodes, cores, n_inter, n_intra,
                          root=root, backend=backend,
                          interpret=interpret).run(values)


def dataplane_hier_reduce(nodes: int, cores: int, n_inter: int, n_intra: int,
                          root: int, values: np.ndarray, op: str,
                          backend: str,
                          interpret: Optional[bool] = None) -> np.ndarray:
    """Two-level reduction data plane: [nodes, cores, m] contributions
    -> the flat [m] op-reduction held by the root."""
    from .hier import hier_host_plan

    return hier_host_plan("reduce", nodes, cores, n_inter, n_intra,
                          root=root, op=op, backend=backend,
                          interpret=interpret).run(values)


def dataplane_hier_allreduce(nodes: int, cores: int, n_inter: int,
                             n_intra: int, root: int, values: np.ndarray,
                             op: str, backend: str,
                             interpret: Optional[bool] = None) -> np.ndarray:
    """Two-level all-reduction data plane: [nodes, cores, m] in ->
    [nodes, cores, m] out, every rank holding the composed reduction."""
    from .hier import hier_host_plan

    return hier_host_plan("allreduce", nodes, cores, n_inter, n_intra,
                          root=root, op=op, backend=backend,
                          interpret=interpret).run(values)


def dataplane_hier_allgather(nodes: int, cores: int, n_inter: int,
                             n_intra: int, values: np.ndarray, backend: str,
                             interpret: Optional[bool] = None) -> np.ndarray:
    """Two-level allgather data plane: [nodes, cores, e] contributions
    -> the replicated [nodes*cores, e] rank-major gathered result."""
    from .hier import hier_host_plan

    return hier_host_plan("allgather", nodes, cores, n_inter, n_intra,
                          backend=backend, interpret=interpret).run(values)
