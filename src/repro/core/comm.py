"""Communicator front-end: plan once, execute many.

The paper's headline split -- an O(log p) one-time schedule
*computation* fully decoupled from the n-1+ceil(log2 p) *execution*
rounds -- deserves an API with the same shape.  This module provides it,
following the communicator/plan separation MPI-style libraries use for
exactly this collective family (Träff, arXiv:2407.18004):

  * :class:`CirculantComm` binds the static context (mesh, axis,
    round-step backend, cost model) once;
  * ``comm.plan(kind, payload_spec, ...)`` precomputes **everything**
    host-side -- the cached schedule bundle, the clamped per-round slot
    tables, the per-round ppermute rotations, the round-step backend
    handle, and the jit-compiled executor -- into an immutable
    :class:`CollectivePlan`;
  * ``plan(payload)`` runs only the traced rounds: no schedule or
    slot-table work happens per call, just a payload-spec check and the
    jit dispatch;
  * ``comm.broadcast(...)`` / ``allgather`` / ``allgatherv`` /
    ``reduce_scatter`` / ``reduce`` / ``allreduce`` / ``allbroadcast``
    are thin plan-cache lookups, so casual call sites get plan reuse
    for free.  The legacy ``circulant_*`` functions in
    :mod:`repro.core.collectives` are shims over these.

Payloads are arbitrary **pytrees**: the plan flattens the tree, splits
every leaf into the same number of blocks n (per-leaf block size
``ceil(leaf_elems / n)``, so ragged leaves just pad their last block),
and runs **one shared schedule** for all leaves -- each communication
round is one ``ppermute`` per leaf on the same rotation, so the round
count stays the single-collective optimum regardless of tree size, and
leaves keep their dtypes (no flatten-to-float32 detour).

Plans are stored in the engine's process-wide spec-keyed plan cache
(:func:`repro.core.engine.cached_plan`), keyed on (mesh, axis, backend,
model, kind, payload spec, resolved block count, root, op): planning
the same collective twice returns the *same* object -- including
``n_blocks=None`` vs an explicit ``n_blocks`` equal to the cost-model
optimum -- and the first execution's XLA compilation is shared by every
later call with the same spec.

The module also hosts the :class:`HostDataPlan` certification path: the
single-process executions of the full data plane (kernel rows standing
in for the p ranks, a row rotation as the network exchange) that
:mod:`repro.core.simulator` asserts bit-exact against its
message-passing reference -- routed through the same plan cache, so
certification sweeps reuse slot tables and step handles too.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Callable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from . import tracing
from .costmodel import (
    DEFAULT_MODEL,
    CommModel,
    optimal_num_blocks_allgather,
    optimal_num_blocks_bcast,
    optimal_num_blocks_reduce,
)
from .engine import ScheduleBundle, cached_plan, get_bundle
from .roundstep import (
    BACKENDS,
    PhaseStatic,
    allgather_phase_static,
    broadcast_phase_static,
    broadcast_slot_plan,
    get_round_step,
    reduce_phase_static,
    reduce_slot_plan,
    scatter_phase_static,
    scatter_slot_plan,
)
from .tracing import scope, span

__all__ = [
    "KINDS",
    "PayloadSpec",
    "payload_spec",
    "validate_payload",
    "CollectivePlan",
    "CirculantComm",
    "get_comm",
    "HostDataPlan",
    "host_plan",
]

#: Collective kinds a plan can be built for.  ``"allbroadcast"`` is the
#: family name (arXiv:2407.18004) for the all-to-all broadcast and
#: canonicalizes to ``"allgather"`` -- both resolve to the same plan.
KINDS = (
    "broadcast",
    "allgather",
    "allgatherv",
    "reduce_scatter",
    "reduce",
    "allreduce",
    "allbroadcast",
    "quantized_allreduce",
)

_CANONICAL_KIND = {"allbroadcast": "allgather"}


# ------------------------------------------------------------- payload spec


@dataclass(frozen=True)
class PayloadSpec:
    """Hashable shape/dtype signature of a pytree payload.

    ``treedef`` is the jax tree structure; ``leaves`` is a tuple of
    ``(shape, dtype)`` per leaf in flatten order.  Two payloads with
    equal specs share one plan (and one compiled executor).
    """

    treedef: Any
    leaves: Tuple[Tuple[Tuple[int, ...], np.dtype], ...]

    @property
    def num_leaves(self) -> int:
        return len(self.leaves)

    def describe(self) -> str:
        body = ", ".join(f"{s}:{np.dtype(d).name}" for s, d in self.leaves)
        return f"{self.treedef} [{body}]"


def payload_spec(payload: Any) -> PayloadSpec:
    """The :class:`PayloadSpec` of a payload pytree.

    Leaves may be jax/NumPy arrays or ``jax.ShapeDtypeStruct``s (so
    specs can be built without materializing data).  Passing an existing
    spec returns it unchanged.
    """
    if isinstance(payload, PayloadSpec):
        return payload
    leaves, treedef = jax.tree.flatten(payload)
    entries = []
    for leaf in leaves:
        if not hasattr(leaf, "shape") or not hasattr(leaf, "dtype"):
            leaf = np.asarray(leaf)
        entries.append((tuple(int(s) for s in leaf.shape), np.dtype(leaf.dtype)))
    return PayloadSpec(treedef=treedef, leaves=tuple(entries))


# ------------------------------------------------------------ small helpers


def validate_payload(spec: PayloadSpec, payload: Any) -> None:
    """Assert ``payload`` matches ``spec`` (tree structure, per-leaf
    shape and dtype) with a precise diagnostic.  Shared by every plan
    front-end (:class:`CollectivePlan` here, ``HierPlan`` in
    :mod:`repro.core.hier`), so the validation contract cannot diverge.
    """
    leaves, treedef = jax.tree.flatten(payload)
    if treedef != spec.treedef:
        raise ValueError(
            f"payload tree {treedef} does not match the plan spec "
            f"{spec.treedef}"
        )
    for i, (leaf, (shape, dtype)) in enumerate(zip(leaves, spec.leaves)):
        if not hasattr(leaf, "shape") or not hasattr(leaf, "dtype"):
            leaf = np.asarray(leaf)
        got_shape = tuple(int(s) for s in leaf.shape)
        got_dtype = np.dtype(leaf.dtype)
        if got_shape != shape or got_dtype != dtype:
            raise ValueError(
                f"payload leaf {i} is {got_shape}:{got_dtype.name}, "
                f"plan expects {shape}:{np.dtype(dtype).name}"
            )


def _rot_perm(p: int, s: int):
    """Static ppermute pairs for the rotation r -> (r + s) % p."""
    return [(r, (r + s) % p) for r in range(p)]


def _split_blocks(flat: jnp.ndarray, n: int, step,
                  qblock: Optional[int] = None):
    """Split a flat vector into n blocks + 1 garbage slot, ``[n+1,
    *slot]`` in the round step's slot layout (``ceil(len/n)`` elements
    per block, zero padded at the tail).  With ``qblock`` the blocks
    hold whole quantization blocks, so schedule blocks and quantization
    blocks never straddle each other (one scale vector per schedule
    block).  A tile-stacked quantized block holds the quantization
    blocks a flat one would (``_qblock_rows``), and its slot's rows past
    them are zero, so the blocks' contents do not depend on the slot's
    layout.  Returns ``(buffer, slot)``."""
    size = flat.shape[0]
    slot = step.slot_shape(-(-size // n), flat.dtype, qblock)
    if qblock is None or len(slot) == 1:
        flat = jnp.pad(flat, (0, n * math.prod(slot) - size))
        blocks = flat.reshape((n,) + slot)
    else:
        nq = _qblock_rows(size, n, qblock)
        blocks = jnp.pad(flat, (0, n * nq * qblock - size))
        blocks = jnp.pad(blocks.reshape(n, nq, qblock),
                         ((0, 0), (0, slot[0] - nq), (0, 0)))
    garbage = jnp.zeros((1,) + slot, flat.dtype)
    return jnp.concatenate([blocks, garbage], axis=0), slot


def _qblock_rows(size: int, n: int, qblock: int) -> int:
    """Quantization blocks that hold a leaf's schedule block on the
    quantized wire: ``ceil(size / n)`` elements in whole qblocks."""
    return -(-size // (n * qblock))


def _leaf_elems(shape: Tuple[int, ...]) -> int:
    out = 1
    for s in shape:
        out *= int(s)
    return out


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise ValueError(msg)


def _tree_executor(shard_fn: Callable, treedef: Any) -> Callable:
    """Wrap a leaves-in/leaves-out shard_map callable as payload->payload."""
    def execute(payload):
        leaves = treedef.flatten_up_to(payload)
        return jax.tree.unflatten(treedef, list(shard_fn(*leaves)))

    return execute


def _acc_dtype(dt: np.dtype):
    """Accumulation dtype for the reduce-scatter partials: sub-float32
    floats (bf16/f16) widen to float32 for stable sums; everything else
    (int32/int64/float32/float64) accumulates natively, so integer sums
    are bit-exact."""
    if jnp.issubdtype(dt, jnp.inexact) and np.dtype(dt).itemsize < 4:
        return jnp.float32
    return dt


# --------------------------------------------------------- phase bodies
#
# The per-collective round loops, factored as *phase* helpers on lists
# of per-leaf flat vectors: each takes a rank index along ONE mesh axis
# and runs that axis' rounds through the shared RoundStep backend,
# looping leaves *inside* the round loop -- every round is one ppermute
# per leaf on the same rotation, so all leaves ride one shared schedule
# (the round count is the single-collective optimum regardless of tree
# size).  The flat lowerings below wrap exactly one phase in a
# one-axis shard_map; the hierarchical layer (repro.core.hier) chains
# two of them along different axes inside one body -- ONE copy of each
# round loop serves both.


def _bcast_phase(flats, n, recv_slots, send_slots, perms, axis_name, r, step,
                 overlap=False, scales=0):
    """Forward broadcast rounds along ``axis_name``; the root row holds
    the data, every row ends holding all n blocks.  The last ``scales``
    leaves are a quantized wire's per-block scales: their permutes run
    under the ``circulant.scales`` scope.

    With ``overlap=True`` the round loop is double-buffered: round
    t+1's send block is packed from the PRE-update buffer -- a value
    with no data dependence on round t's ppermute result, so XLA can
    schedule the pack while the exchange is in flight -- and the staged
    step patches the single stale case ``recv[t] == send[t+1]`` with
    the received message.  Bit-exact vs the sequential loop (only the
    recv slot changes per round)."""
    with scope(tracing.BCAST):
        recv_t = jnp.asarray(recv_slots)  # [R, p] static slot tables
        send_t = jnp.asarray(send_slots)
        R = recv_t.shape[0]
        bufs, msgs, sizes = [], [], []
        for flat in flats:
            with scope(tracing.SPLIT):
                buf, _ = _split_blocks(flat, n, step)
                buf = buf[None]                       # [1, n+1, *slot]
            bufs.append(buf)
            sizes.append(flat.shape[0])
            msgs.append(step.pack(buf, send_t[0, r][None]))
        plain = len(msgs) - scales
        for t in range(R):
            got = [jax.lax.ppermute(m, axis_name, perms[t])
                   for m in msgs[:plain]]
            with scope(tracing.SCALES):
                got += [jax.lax.ppermute(m, axis_name, perms[t])
                        for m in msgs[plain:]]
            for i in range(len(bufs)):
                if t + 1 < R:
                    if overlap:
                        pre = step.pack(bufs[i], send_t[t + 1, r][None])
                        bufs[i], msgs[i] = step.shuffle_staged(
                            bufs[i], got[i], pre, recv_t[t, r][None],
                            send_t[t + 1, r][None])
                    else:
                        bufs[i], msgs[i] = step.shuffle(
                            bufs[i], got[i], recv_t[t, r][None],
                            send_t[t + 1, r][None])
                else:
                    bufs[i] = step.unpack(bufs[i], got[i],
                                          recv_t[t, r][None])
        with scope(tracing.JOIN):
            return [buf[0, :n].reshape(-1)[:size]
                    for buf, size in zip(bufs, sizes)]


def _reduce_phase(flats, n, fwd_slots, acc_slots, perms, axis_name, r,
                  idents, op, step, overlap=False):
    """Reversed (reduction) rounds along ``axis_name``; the root row
    ends with the op-reduction, every other row is drained to the
    identity.

    With ``overlap=True`` the captured round-t+1 forward block is packed
    from the PRE-accumulate buffer (overlapping the round-t exchange)
    and the staged step patches the coincident ``fwd == acc`` case with
    the freshly combined value -- bit-exact vs the sequential loop."""
    with scope(tracing.REDUCE):
        F = jnp.asarray(fwd_slots)  # [R, p] static slot tables (root row
        A = jnp.asarray(acc_slots)  # pinned to the identity slot n+1)
        R = F.shape[0]
        garbage = jnp.full((1,), n, jnp.int32)
        bufs, msgs, sizes = [], [], []
        for flat, ident in zip(flats, idents):
            with scope(tracing.SPLIT):
                buf, slot = _split_blocks(flat, n, step)  # [n+1, *slot]
                buf = jnp.concatenate(
                    [buf, jnp.full((1,) + slot, ident, buf.dtype)], axis=0
                )[None]                                   # [1, n+2, *slot]
            # Initial capture+drain of round 0's forwarded partial.
            buf, msg = step.acc_shuffle(
                buf, jnp.zeros((1,) + slot, buf.dtype), garbage,
                F[0, r][None], op=op)
            bufs.append(buf)
            msgs.append(msg)
            sizes.append(flat.shape[0])
        for t in range(R):
            got = [jax.lax.ppermute(m, axis_name, perms[t]) for m in msgs]
            nxt = F[t + 1, r][None] if t + 1 < R else garbage
            for i in range(len(bufs)):
                # accumulate round t's incoming partial, then
                # capture+drain round t+1's forward (each partial flows
                # along exactly one tree edge).
                if overlap:
                    pre = step.pack(bufs[i], nxt)
                    bufs[i], msgs[i] = step.acc_shuffle_staged(
                        bufs[i], got[i], pre, A[t, r][None], nxt, op=op)
                else:
                    bufs[i], msgs[i] = step.acc_shuffle(
                        bufs[i], got[i], A[t, r][None], nxt, op=op)
        with scope(tracing.JOIN):
            return [buf[0, :n].reshape(-1)[:size]
                    for buf, size in zip(bufs, sizes)]


def _allgather_phase(flats, n, recv_slots, skips, perms, axis_name, r,
                     p, step, overlap=False):
    """All-to-all broadcast rounds along ``axis_name``: every row
    contributes its flat vector, every row ends with the [p * len]
    rank-major concatenation.  One clamped [R, p] slot table serves
    recv AND send: by Condition 2 the send slot of root row j is the
    recv slot of the shifted virtual rank, so both are gathers of the
    same table."""
    with scope(tracing.ALLGATHER):
        S = jnp.asarray(recv_slots)  # [R, p] static slot table
        R = S.shape[0]
        base = (r - jnp.arange(p)) % p  # virtual rank of root row j at r

        def send_slots_at(t):
            return S[t][(base + skips[t]) % p]

        bufs, sizes = [], []
        for flat in flats:
            # buffers[j] holds root j's blocks; only the own row is filled.
            with scope(tracing.SPLIT):
                own, _ = _split_blocks(flat, n, step)     # [n+1, *slot]
                buf = jnp.zeros((p,) + own.shape, flat.dtype)
                buf = jax.lax.dynamic_update_slice(buf, own[None],
                                                   (r,) + (0,) * own.ndim)
            bufs.append(buf)
            sizes.append(flat.shape[0])
        msgs = [step.pack(buf, send_slots_at(0)) for buf in bufs]
        for t in range(R):
            got = [jax.lax.ppermute(m, axis_name, perms[t]) for m in msgs]
            for i in range(len(bufs)):
                if t + 1 < R:
                    if overlap:
                        pre = step.pack(bufs[i], send_slots_at(t + 1))
                        bufs[i], msgs[i] = step.shuffle_staged(
                            bufs[i], got[i], pre, S[t][base],
                            send_slots_at(t + 1))
                    else:
                        bufs[i], msgs[i] = step.shuffle(
                            bufs[i], got[i], S[t][base],
                            send_slots_at(t + 1))
                else:
                    bufs[i] = step.unpack(bufs[i], got[i], S[t][base])
        with scope(tracing.JOIN):
            return [buf[:, :n].reshape(p, -1)[:, :size].reshape(-1)
                    for buf, size in zip(bufs, sizes)]


def _qreduce_phase(flats, n, fwd_slots, acc_slots, perms, axis_name, r, step,
                   qblock):
    """Quantized-wire reversed (sum) rounds along ``axis_name``: the wire
    carries int8 blocks + per-qblock f32 scales; every requantization's
    error is accumulated into a per-slot error buffer on the rank that
    generated it.  Returns per-leaf ``(buf, err, nb, size)`` with buf/err
    the [1, n+2, *slot] f32 buffers (root row of buf holds the lossy sum;
    err holds each rank's locally generated error in SUM units)."""
    with scope(tracing.QREDUCE):
        F = jnp.asarray(fwd_slots)  # [R, p] static slot tables (root row
        A = jnp.asarray(acc_slots)  # pinned to the identity slot n+1)
        R = F.shape[0]
        garbage = jnp.full((1,), n, jnp.int32)
        bufs, errs, qmsgs, smsgs, metas = [], [], [], [], []
        for flat in flats:
            with scope(tracing.SPLIT):
                buf, slot = _split_blocks(flat, n, step, qblock)
                nb = math.prod(slot) // qblock
                # slot n+1 is the sum identity (zero), as in _reduce_phase.
                buf = jnp.concatenate(
                    [buf, jnp.zeros((1,) + slot, buf.dtype)], axis=0
                )[None]                                   # [1, n+2, *slot]
                err = jnp.zeros_like(buf)
            # Initial capture+drain of round 0's forwarded partial (zero
            # message: dequant(0, 0) == 0 folds into the garbage slot).
            buf, err, qm, sm = step.qacc_shuffle(
                buf, err, jnp.zeros((1,) + slot, jnp.int8),
                jnp.zeros((1, nb), jnp.float32), garbage, F[0, r][None])
            bufs.append(buf)
            errs.append(err)
            qmsgs.append(qm)
            smsgs.append(sm)
            metas.append((nb, flat.shape[0]))
        for t in range(R):
            got_q = [jax.lax.ppermute(m, axis_name, perms[t]) for m in qmsgs]
            with scope(tracing.SCALES):
                got_s = [jax.lax.ppermute(m, axis_name, perms[t])
                         for m in smsgs]
            nxt = F[t + 1, r][None] if t + 1 < R else garbage
            for i in range(len(bufs)):
                bufs[i], errs[i], qmsgs[i], smsgs[i] = step.qacc_shuffle(
                    bufs[i], errs[i], got_q[i], got_s[i], A[t, r][None], nxt)
        return [(buf, err) + meta
                for buf, err, meta in zip(bufs, errs, metas)]


def _quantized_allreduce_core(flats, n, fwd_slots, acc_slots, recv_slots,
                              send_slots, red_perms, bc_perms, axis_name, r,
                              root, step, qblock):
    """int8-on-the-wire allreduce body (sum): quantized reversed reduce
    to ``root``, root-side final requantization, then the forward
    broadcast of the int8 blocks + scales, dequantized on every rank.

    Returns ``(sums, errs)``: per-leaf flat f32 lossy sums (identical on
    every rank) and per-leaf flat f32 error vectors in SUM units -- each
    rank holds only its locally generated quantization error, and

        exact_sum == lossy_sum + psum(err)

    holds bit-for-bit up to f32 accumulation order (the error-feedback
    completeness invariant; see optim/compression.py).
    """
    from repro.kernels.quant_ops import (
        dequant_blocks,
        quant_blocks,
        quant_error,
    )

    reduced = _qreduce_phase(flats, n, fwd_slots, acc_slots, red_perms,
                             axis_name, r, step, qblock)
    q_flats, s_flats, err_blocks, sizes, nbs = [], [], [], [], []
    for buf, err, nb, size in reduced:
        with scope(tracing.REQUANT):
            data = buf[0, :n]                          # [n, *slot]
            q, sc = quant_blocks(data.reshape(n * nb, qblock))
            eps = quant_error(data.reshape(n * nb, qblock), q,
                              sc).reshape(data.shape)
            is_root = r == root
            # Non-root rows were drained by the reduce, but capped
            # re-sends can leave stale partials in slot n-1 -- zero them
            # exactly as _lower_broadcast zeroes non-root payloads.
            q_flats.append(jnp.where(is_root, q.reshape(-1),
                                     jnp.zeros((q.size,), jnp.int8)))
            s_flats.append(jnp.where(is_root, sc.reshape(-1),
                                     jnp.zeros((n * nb,), jnp.float32)))
            # The final quantization error belongs to the root (the rank
            # that generated it); everyone else contributes zero.
            err_blocks.append(
                err[0, :n] + jnp.where(is_root, eps, jnp.zeros_like(eps)))
        sizes.append(size)
        nbs.append(nb)
    outs = _bcast_phase(q_flats + s_flats, n, recv_slots, send_slots,
                        bc_perms, axis_name, r, step, scales=len(s_flats))
    L = len(q_flats)
    sums, errs = [], []
    with scope(tracing.JOIN):
        for i in range(L):
            nb, size = nbs[i], sizes[i]
            nq = _qblock_rows(size, n, qblock)
            red = dequant_blocks(
                outs[i].reshape(n * nb, qblock),
                outs[L + i].reshape(n * nb, 1),
            )
            # The error past ``size`` and in the slots' pad rows is
            # exactly zero, so truncation drops no error mass: every
            # rank pads with exact zeros, a zero lane quantizes to 0
            # with zero error, and quant_error zeroes the non-finite
            # lanes of a flagged block.  (A fold of the tail into the
            # last element would, under vmap, make the TPU compiler lay
            # the requantization's rank axis minor.)
            for out, x in ((sums, red), (errs, err_blocks[i])):
                out.append(x.reshape(n, nb, qblock)[:, :nq]
                           .reshape(-1)[:size])
    return sums, errs


def circulant_qallreduce_body(flats, axis_name: str, p: int, *,
                              n_blocks: Optional[int] = None, root: int = 0,
                              backend: str = "jnp",
                              qblock: Optional[int] = None):
    """Run the quantized circulant allreduce inside an existing shard_map.

    ``flats``: list of flat f32 vectors (every rank passes the same
    shapes).  Returns ``(sums, errs)`` as in
    :func:`_quantized_allreduce_core`; the caller divides by ``p`` for a
    mean.  Static planning (block count, slot tables, rotations, step
    handle) is resolved once per (p, sizes, n, root, qblock, backend)
    via the process-wide plan cache -- trainers reuse one frozen plan
    per bucket spec across steps.  For a standalone collective use
    ``CirculantComm.plan("quantized_allreduce", ...)`` instead.
    """
    from repro.kernels.quant_ops import QBLOCK

    qblock = QBLOCK if qblock is None else int(qblock)
    sizes = tuple(int(f.shape[0]) for f in flats)
    if p == 1:
        return list(flats), [jnp.zeros_like(f) for f in flats]
    (n, fwd, acc, recv, send, red_perms, bc_perms) = _qsync_static(
        p, sizes, n_blocks, int(root), qblock, backend)
    step = get_round_step(backend)
    r = jax.lax.axis_index(axis_name)
    return _quantized_allreduce_core(
        flats, n, fwd, acc, recv, send, red_perms, bc_perms, axis_name, r,
        int(root), step, qblock)


def _qsync_static(p: int, sizes: Tuple[int, ...], n_blocks: Optional[int],
                  root: int, qblock: int, backend: str):
    """Cached static tables for :func:`circulant_qallreduce_body`."""
    key = ("qsync", p, sizes, n_blocks, root, qblock, backend)

    def build():
        # Wire bytes are ~1 per element (int8 + amortized scales).
        total = max(1, sum(sizes))
        n = n_blocks or max(
            1, optimal_num_blocks_reduce(p, total, DEFAULT_MODEL))
        n = min(n, max(1, -(-max(sizes) // qblock)))
        bundle = get_bundle(p, root)
        fwd, acc, ks_r = reduce_slot_plan(bundle, n)
        recv, send, ks_b = broadcast_slot_plan(bundle, n)
        red_perms = [_rot_perm(p, (p - bundle.skip[int(k)]) % p)
                     for k in ks_r]
        bc_perms = [_rot_perm(p, bundle.skip[int(k)]) for k in ks_b]
        return (n, fwd, acc, recv, send, red_perms, bc_perms)

    return cached_plan(key, build)


class SyncCounters(NamedTuple):
    """Static counters of one :func:`circulant_qallreduce_body` call, as
    :class:`CollectivePlan` counts a plan's: the block count and rounds,
    the ``ppermute``s issued (two per leaf per round: int8 blocks and
    their scales), the bytes one rank sends, the part of those bytes
    that carries the per-block f32 scales, and the buckets whose
    quantized slot the round step lays out as a tile stack."""

    n_blocks: int
    rounds: int
    permutes: int
    wire_bytes: int
    scales_wire_bytes: int
    tiled_qslots: int


def circulant_qallreduce_counters(sizes, p: int, *,
                                  n_blocks: Optional[int] = None,
                                  root: int = 0, backend: str = "jnp",
                                  qblock: Optional[int] = None
                                  ) -> SyncCounters:
    """:class:`SyncCounters` of :func:`circulant_qallreduce_body` on flat
    f32 vectors of ``sizes`` elements, with the same options; nothing
    moves on one rank."""
    from repro.kernels.quant_ops import QBLOCK

    qblock = QBLOCK if qblock is None else int(qblock)
    sizes = tuple(int(s) for s in sizes)
    if p == 1:
        return SyncCounters(1, 0, 0, 0, 0, 0)
    n = _qsync_static(p, sizes, n_blocks, int(root), qblock, backend)[0]
    rounds = get_bundle(p, int(root)).allreduce_rounds(n)
    step = get_round_step(backend)
    msgs = [_leaf_messages("quantized_allreduce", (p, size), np.float32, p,
                           n, step, qblock, None) for size in sizes]
    return SyncCounters(
        n_blocks=n, rounds=rounds, permutes=2 * rounds * len(sizes),
        wire_bytes=_wire_bytes("quantized_allreduce", msgs, rounds),
        # messages 1 and 3 of a leaf are its scales (reduce, broadcast)
        scales_wire_bytes=_wire_bytes("quantized_allreduce",
                                      [m[1::2] for m in msgs], rounds),
        tiled_qslots=_tiled_qslots("quantized_allreduce", msgs))


# ------------------------------------------------------- device lowerings
#
# One lowering per collective kind: each wraps one phase helper (or a
# bespoke loop for the irregular kinds) in a single one-axis shard_map
# and returns ``execute(payload) -> payload``.


def _lower_broadcast(mesh: Mesh, axis_name: str, bundle: ScheduleBundle,
                     n: int, root: int, backend: str,
                     spec: PayloadSpec, overlap: bool = False) -> Callable:
    p = bundle.p
    recv_slots, send_slots, ks = broadcast_slot_plan(bundle, n)
    step = get_round_step(backend)
    perms = [_rot_perm(p, bundle.skip[int(k)]) for k in ks]
    L = spec.num_leaves

    def body(*shards):
        r = jax.lax.axis_index(axis_name)
        flats, shapes = [], []
        with scope(tracing.SPLIT):
            for xs in shards:
                flat = xs.reshape(-1)
                flats.append(jnp.where(r == root, flat,
                                       jnp.zeros_like(flat)))
                shapes.append(xs.shape)
        outs = _bcast_phase(flats, n, recv_slots, send_slots, perms,
                            axis_name, r, step, overlap=overlap)
        with scope(tracing.JOIN):
            return tuple(f.reshape(shape) for f, shape in zip(outs, shapes))

    shard_fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis_name),) * L,
        out_specs=(P(axis_name),) * L,
        # jax has no replication rule for pallas_call inside shard_map.
        check_vma=(backend == "jnp"),
    )

    return _tree_executor(shard_fn, spec.treedef)


def _lower_allgather(mesh: Mesh, axis_name: str, bundle: ScheduleBundle,
                     n: int, backend: str, spec: PayloadSpec,
                     overlap: bool = False) -> Callable:
    p = bundle.p
    recv_slots, _, ks = broadcast_slot_plan(bundle, n)
    step = get_round_step(backend)
    perms = [_rot_perm(p, bundle.skip[int(k)]) for k in ks]
    skips = [int(bundle.skip[int(k)]) for k in ks]
    L = spec.num_leaves

    def body(*shards):
        r = jax.lax.axis_index(axis_name)
        with scope(tracing.SPLIT):
            flats = [xs.reshape(-1) for xs in shards]
        shapes = [xs.shape for xs in shards]
        outs = _allgather_phase(flats, n, recv_slots, skips, perms,
                                axis_name, r, p, step, overlap=overlap)
        with scope(tracing.JOIN):
            return tuple(
                f.reshape((p * shape[0],) + tuple(shape[1:]))
                for f, shape in zip(outs, shapes)
            )

    shard_fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis_name),) * L,
        out_specs=(P(),) * L,
        check_vma=False,  # result is replicated by construction
    )

    return _tree_executor(shard_fn, spec.treedef)


def _lower_allgatherv(mesh: Mesh, axis_name: str, bundle: ScheduleBundle,
                      n: int, backend: str, spec: PayloadSpec,
                      sizes_canon: Tuple[Tuple[int, ...], ...]) -> Callable:
    p = bundle.p
    recv_slots, _, ks = broadcast_slot_plan(bundle, n)
    step = get_round_step(backend)
    R = len(ks)
    perms = [_rot_perm(p, bundle.skip[int(k)]) for k in ks]
    skips = [int(bundle.skip[int(k)]) for k in ks]
    caps = [shape[1] for shape, _ in spec.leaves]
    # Static per-(leaf, root) slot shapes: the wire volume tracks
    # sum(sizes), not p*max(sizes) (paper Figure 2's degenerate case).
    slots_all = [[step.slot_shape(max(1, -(-s // n)), dtype) for s in sizes]
                 for (_, dtype), sizes in zip(spec.leaves, sizes_canon)]
    L = spec.num_leaves

    def body(*shards):
        with scope(tracing.ALLGATHERV):
            r = jax.lax.axis_index(axis_name)
            S = jnp.asarray(recv_slots)  # [R, p] static slot table
            allbufs: List[List[jnp.ndarray]] = []
            for xs, slots, cap in zip(shards, slots_all, caps):
                with scope(tracing.SPLIT):
                    flat = xs.reshape(-1)  # own contribution padded to cap
                    bufs = []
                    for j in range(p):
                        e = math.prod(slots[j])
                        pj = jnp.pad(flat[: min(cap, n * e)],
                                     (0, max(0, n * e - cap)))
                        own = jnp.concatenate(
                            [pj[: n * e].reshape((n,) + slots[j]),
                             jnp.zeros((1,) + slots[j], xs.dtype)], axis=0)
                        bufs.append(jnp.where(r == j, own,
                                              jnp.zeros_like(own)))
                allbufs.append(bufs)
            for t in range(R):
                sk = skips[t]
                gots, all_slots = [], []
                for bufs in allbufs:
                    parts, slots_r = [], []
                    for j in range(p):
                        ss = S[t][(r - j + sk) % p]
                        slots_r.append(S[t][(r - j) % p])
                        parts.append(
                            step.pack(bufs[j][None], ss[None])[0].reshape(-1))
                    msg = jnp.concatenate(parts)  # [sum of slot sizes]
                    gots.append(jax.lax.ppermute(msg, axis_name, perms[t]))
                    all_slots.append(slots_r)
                for bufs, slots, got, slots_r in zip(allbufs, slots_all, gots,
                                                     all_slots):
                    o = 0
                    for j in range(p):
                        e = math.prod(slots[j])
                        piece = got[o: o + e].reshape(slots[j])[None]
                        bufs[j] = step.unpack(bufs[j][None], piece,
                                              slots_r[j][None])[0]
                        o += e
            outs = []
            with scope(tracing.JOIN):
                for bufs, sizes, cap in zip(allbufs, sizes_canon, caps):
                    rows = []
                    for j in range(p):
                        rj = bufs[j][:n].reshape(-1)[: sizes[j]]
                        rows.append(jnp.pad(rj, (0, cap - sizes[j])))
                    outs.append(jnp.stack(rows))
            return tuple(outs)

    shard_fn = jax.shard_map(
        body, mesh=mesh, in_specs=(P(axis_name),) * L,
        out_specs=(P(),) * L, check_vma=False,
    )

    return _tree_executor(shard_fn, spec.treedef)


def _lower_reduce(mesh: Mesh, axis_name: str, bundle: ScheduleBundle,
                  n: int, root: int, op: str, backend: str,
                  spec: PayloadSpec, overlap: bool = False) -> Callable:
    from repro.kernels.reduce_ops import op_identity

    p = bundle.p
    fwd_slots, acc_slots, ks = reduce_slot_plan(bundle, n)
    step = get_round_step(backend)
    perms = [_rot_perm(p, (p - bundle.skip[int(k)]) % p) for k in ks]
    idents = [op_identity(op, dt) for _, dt in spec.leaves]
    L = spec.num_leaves

    def body(*shards):
        r = jax.lax.axis_index(axis_name)
        with scope(tracing.SPLIT):
            flats = [xs.reshape(-1) for xs in shards]
        shapes = [xs.shape for xs in shards]
        outs = _reduce_phase(flats, n, fwd_slots, acc_slots, perms,
                             axis_name, r, idents, op, step,
                             overlap=overlap)
        with scope(tracing.JOIN):
            return tuple(
                jnp.where(r == root, f, jnp.zeros_like(f)).reshape(shape)
                for f, shape in zip(outs, shapes)
            )

    shard_fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis_name),) * L,
        out_specs=(P(axis_name),) * L,
        check_vma=(backend == "jnp"),
    )

    return _tree_executor(shard_fn, spec.treedef)


def _lower_reduce_scatter(mesh: Mesh, axis_name: str, bundle: ScheduleBundle,
                          n: int, backend: str, spec: PayloadSpec,
                          overlap: bool = False) -> Callable:
    p = bundle.p
    fwd_slots, acc_slots, ks = scatter_slot_plan(bundle, n)
    step = get_round_step(backend)
    R = len(ks)
    perms = [_rot_perm(p, (p - bundle.skip[int(k)]) % p) for k in ks]
    shard_l = [shape[1] // p for shape, _ in spec.leaves]
    slot_l = [step.slot_shape(max(1, -(-shard // n)), _acc_dtype(dt))
              for shard, (_, dt) in zip(shard_l, spec.leaves)]
    L = spec.num_leaves

    def body(*shards):
        with scope(tracing.SCATTER):
            r = jax.lax.axis_index(axis_name)
            F = jnp.asarray(fwd_slots)  # [R, p] static slot tables
            A = jnp.asarray(acc_slots)
            base = (r - jnp.arange(p)) % p
            garbage = jnp.full((p,), n, jnp.int32)
            bufs, msgs, meta = [], [], []
            for xs, shard, slot in zip(shards, shard_l, slot_l):
                with scope(tracing.SPLIT):
                    rows = xs[0].reshape(p, shard)  # contribution per root
                    rows = jnp.pad(
                        rows, ((0, 0), (0, n * math.prod(slot) - shard)))
                    # Partials accumulate in _acc_dtype: native for ints
                    # (so the sums are bit-exact) and >= float32 floats,
                    # widened to float32 for bf16/f16 stability.
                    buf = jnp.concatenate(
                        [rows.reshape((p, n) + slot),
                         jnp.zeros((p, 1) + slot, xs.dtype)],
                        axis=1,
                    ).astype(_acc_dtype(xs.dtype))
                # Initial capture+drain of round 0's forwarded partials.
                buf, msg = step.acc_shuffle(
                    buf, jnp.zeros((p,) + slot, buf.dtype), garbage,
                    F[0][base], op="sum")
                bufs.append(buf)
                msgs.append(msg)
                meta.append((shard, slot, xs.dtype))
            for t in range(R):
                got = [jax.lax.ppermute(m, axis_name, perms[t])
                       for m in msgs]
                nxt = F[t + 1][base] if t + 1 < R else garbage
                for i in range(L):
                    if overlap:
                        pre = step.pack(bufs[i], nxt)
                        bufs[i], msgs[i] = step.acc_shuffle_staged(
                            bufs[i], got[i], pre, A[t][base], nxt, op="sum")
                    else:
                        bufs[i], msgs[i] = step.acc_shuffle(
                            bufs[i], got[i], A[t][base], nxt, op="sum")
            outs = []
            with scope(tracing.JOIN):
                for buf, (shard, slot, dt) in zip(bufs, meta):
                    own = jax.lax.dynamic_slice(
                        buf, (r,) + (0,) * (buf.ndim - 1), (1, n) + slot)
                    outs.append(own.reshape(-1)[:shard].astype(dt)[None])
            return tuple(outs)

    shard_fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis_name),) * L,
        out_specs=(P(axis_name),) * L,
        check_vma=(backend == "jnp"),
    )

    return _tree_executor(shard_fn, spec.treedef)


def _lower_quantized_allreduce(mesh: Mesh, axis_name: str,
                               bundle: ScheduleBundle, n: int, root: int,
                               backend: str, spec: PayloadSpec,
                               qblock: int) -> Callable:
    p = bundle.p
    fwd_slots, acc_slots, _ = reduce_slot_plan(bundle, n)
    recv_slots, send_slots, ks_b = broadcast_slot_plan(bundle, n)
    _, _, ks_r = reduce_slot_plan(bundle, n)
    step = get_round_step(backend)
    red_perms = [_rot_perm(p, (p - bundle.skip[int(k)]) % p) for k in ks_r]
    bc_perms = [_rot_perm(p, bundle.skip[int(k)]) for k in ks_b]
    L = spec.num_leaves
    treedef = spec.treedef

    def body(*shards):
        r = jax.lax.axis_index(axis_name)
        with scope(tracing.SPLIT):
            flats = [xs.reshape(-1) for xs in shards]
        shapes = [xs.shape for xs in shards]
        sums, errs = _quantized_allreduce_core(
            flats, n, fwd_slots, acc_slots, recv_slots, send_slots,
            red_perms, bc_perms, axis_name, r, root, step, qblock)
        with scope(tracing.JOIN):
            return (tuple(f.reshape(s) for f, s in zip(sums, shapes))
                    + tuple(f.reshape(s) for f, s in zip(errs, shapes)))

    shard_fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis_name),) * L,
        out_specs=(P(axis_name),) * (2 * L),
        # sums are replicated by construction, errs are genuinely
        # per-rank; vma checking can't express the mix (and pallas has
        # no replication rule anyway).
        check_vma=False,
    )

    def execute(payload):
        leaves = treedef.flatten_up_to(payload)
        outs = list(shard_fn(*leaves))
        return (jax.tree.unflatten(treedef, outs[:L]),
                jax.tree.unflatten(treedef, outs[L:]))

    return execute


# ------------------------------------------------------------ plan objects


@dataclass(frozen=True, eq=False)
class CollectivePlan:
    """A fully precomputed, immutable collective: call it with payloads.

    Everything static was resolved at plan time -- the cached schedule
    bundle, the clamped per-round slot tables, the per-round rotations,
    the round-step backend handle, and the jit-compiled executor.
    ``plan(payload)`` validates the payload against ``spec`` and
    dispatches the compiled rounds; there is **no** schedule or
    slot-table work per call.  Plans are cached process-wide: building
    the same plan twice returns the same object (compare with ``is``).
    """

    kind: str
    spec: PayloadSpec
    p: int
    root: int
    op: Optional[str]
    n_blocks: int
    rounds: int
    backend: str
    axis_name: str
    qblock: Optional[int] = None
    #: True when the executor runs the overlapped (double-buffered)
    #: round loop: the next round's block is packed from the pre-update
    #: buffer concurrently with the in-flight exchange, then patched by
    #: the staged step.  Bit-exact vs the sequential executor.
    overlap: bool = False
    #: ``ppermute``s one call issues (one per leaf per round, two on the
    #: quantized wire: int8 blocks and their scales), the bytes one rank
    #: sends in a call, the payload leaves whose slots the round step
    #: lays out as tile stacks, and of a quantized plan the leaves whose
    #: quantized reduce slot it lays out so; static, counted at plan
    #: time.
    permutes: int = 0
    wire_bytes: int = 0
    tiled_leaves: int = 0
    tiled_qslots: int = 0
    #: Auditable per-phase schedule statics (the exact cached slot
    #: tables the executor closed over); () on the p == 1 fast path.
    #: Checked by repro.analysis.planaudit without executing a round.
    statics: Tuple[PhaseStatic, ...] = field(repr=False, default=())
    _execute: Optional[Callable] = field(repr=False, default=None)

    def __call__(self, payload: Any) -> Any:
        """Execute the collective.  ``quantized_allreduce`` plans return
        a ``(sums, errors)`` pair of payload-shaped trees; every other
        kind returns one payload-shaped tree."""
        with span(tracing.CALL):
            with span(tracing.VALIDATE):
                validate_payload(self.spec, payload)
            if self._execute is None:  # p == 1 fast path: nothing moves
                return payload
            with span(tracing.EXECUTE):
                return self._execute(payload)

    def describe(self) -> str:
        """One-line human summary of the plan."""
        extra = f" op={self.op}" if self.op else ""
        if self.qblock is not None:
            extra += f" qblock={self.qblock} tiled_qslots={self.tiled_qslots}"
        if self.overlap:
            extra += " overlap"
        return (f"{self.kind} p={self.p} root={self.root} "
                f"n={self.n_blocks} rounds={self.rounds} "
                f"permutes={self.permutes} wire_bytes={self.wire_bytes} "
                f"tiled_leaves={self.tiled_leaves} "
                f"backend={self.backend}{extra} spec={self.spec.describe()}")


def _plan_statics(kind: str, bundle: ScheduleBundle, n: int,
                  axis: Optional[str] = None,
                  overlap: bool = False) -> Tuple[PhaseStatic, ...]:
    """The per-phase audit records of a flat collective, in execution
    order (the reversed reduction phase precedes the forward broadcast
    phase for the composed all-reductions)."""
    if kind == "broadcast":
        return (broadcast_phase_static(bundle, n, axis=axis,
                                       overlap=overlap),)
    if kind in ("allgather", "allgatherv"):
        return (allgather_phase_static(bundle, n, axis=axis,
                                       overlap=overlap),)
    if kind == "reduce_scatter":
        return (scatter_phase_static(bundle, n, axis=axis, overlap=overlap),)
    if kind == "reduce":
        return (reduce_phase_static(bundle, n, axis=axis, overlap=overlap),)
    # allreduce / quantized_allreduce: reversed reduce then broadcast
    return (reduce_phase_static(bundle, n, axis=axis, overlap=overlap),
            broadcast_phase_static(bundle, n, axis=axis, overlap=overlap))


def _message_slot(step, elems: int, dtype, n: int,
                  qblock: Optional[int] = None) -> Tuple[int, ...]:
    """Slot of one round's message for one leaf: a flat vector of
    ``elems`` elements split into ``n`` blocks, in the round step's slot
    layout (as :func:`_split_blocks` lays them out)."""
    return step.slot_shape(-(-elems // n), dtype, qblock)


def _messages_bytes(msgs) -> int:
    """Bytes of ``(slot, dtype, rows)`` messages: ``rows`` slots each."""
    return sum(rows * math.prod(slot) * np.dtype(dt).itemsize
               for slot, dt, rows in msgs)


def _leaf_messages(kind: str, shape, dtype, p: int, n: int, step,
                   qblock: Optional[int], sizes) -> list:
    """``(slot, dtype, rows)`` of each message one round of a flat
    collective sends for one payload leaf (``sizes``: the leaf's
    canonical allgatherv sizes)."""
    def msg(elems, dt, rows=1):
        return _message_slot(step, elems, dt, n), dt, rows

    if kind == "allgatherv":
        return [msg(max(1, s), dtype) for s in sizes]
    if kind == "allgather":
        return [msg(shape[0] // p * _leaf_elems(shape[1:]), dtype, p)]
    if kind == "reduce_scatter":
        return [msg(max(1, shape[1] // p), _acc_dtype(dtype), p)]
    if kind == "quantized_allreduce":
        # one reduce round (int8 blocks + per-qblock f32 scales) and
        # one broadcast round (the same, as two leaves)
        slot = _message_slot(step, _leaf_elems(shape[1:]), np.float32, n,
                             qblock)
        elems = math.prod(slot)
        scales = elems // qblock
        return [(slot, np.int8, 1), ((scales,), np.float32, 1),
                msg(n * elems, np.int8), msg(n * scales, np.float32)]
    return [msg(_leaf_elems(shape[1:]), dtype)]


def _plan_messages(kind: str, spec: PayloadSpec, p: int, n: int, step,
                   qblock: Optional[int], sizes_canon) -> list:
    """Per payload leaf, :func:`_leaf_messages`."""
    return [_leaf_messages(kind, shape, dtype, p, n, step, qblock,
                           sizes_canon[i] if sizes_canon else None)
            for i, (shape, dtype) in enumerate(spec.leaves)]


def _wire_bytes(kind: str, leaves, rounds: int) -> int:
    """Bytes one rank sends in one call of a flat collective: each
    round's messages (``leaves``, from :func:`_plan_messages`), summed
    over the leaves, times the rounds."""
    if kind == "quantized_allreduce":
        rounds //= 2  # a round above is one reduce and one broadcast round
    return rounds * sum(_messages_bytes(msgs) for msgs in leaves)


def _tiled_leaves(leaves) -> int:
    """Leaves (``leaves``, from :func:`_plan_messages`) with a message
    slot laid out as a tile stack rather than flat."""
    return sum(any(len(slot) > 1 for slot, _, _ in msgs) for msgs in leaves)


def _tiled_qslots(kind: str, leaves) -> int:
    """Leaves of a quantized plan whose quantized reduce slot (the first
    message of each leaf, the int8 blocks) is a tile stack."""
    if kind != "quantized_allreduce":
        return 0
    return sum(len(msgs[0][0]) > 1 for msgs in leaves)


# --------------------------------------------------------- n-block choice


def _resolve_broadcast(spec: PayloadSpec, p: int, n_blocks: Optional[int],
                       model: CommModel, optimizer) -> int:
    elems, total = [], 0
    for shape, dtype in spec.leaves:
        _require(len(shape) >= 1 and shape[0] == p,
                 "payload leaves must have leading axis == axis size "
                 f"(one slice/rank); got {shape} for p={p}")
        e = _leaf_elems(shape[1:])
        elems.append(e)
        total += e * np.dtype(dtype).itemsize
    n = n_blocks or max(1, optimizer(p, total, model))
    return min(n, max(1, max(elems)))


def _resolve_allgather(spec: PayloadSpec, p: int, n_blocks: Optional[int],
                       model: CommModel) -> int:
    shard_elems, total = [], 0
    for shape, dtype in spec.leaves:
        _require(len(shape) >= 1 and shape[0] % p == 0,
                 f"leading dim {shape[0] if shape else 0} not divisible by "
                 f"axis size {p}")
        e = (shape[0] // p) * _leaf_elems(shape[1:])
        shard_elems.append(e)
        total += e * np.dtype(dtype).itemsize
    n = n_blocks or max(1, optimal_num_blocks_allgather(p, total * p, model))
    return min(n, max(1, max(shard_elems)))


def _resolve_allgatherv(spec: PayloadSpec, p: int, n_blocks: Optional[int],
                        model: CommModel,
                        sizes_canon: Tuple[Tuple[int, ...], ...]) -> int:
    total = 0
    min_pos = None
    for (shape, dtype), sizes in zip(spec.leaves, sizes_canon):
        _require(len(shape) == 2 and shape[0] == p,
                 f"allgatherv leaves must be [p, cap]; got {shape} for p={p}")
        _require(len(sizes) == p, f"sizes must have length p={p}")
        for s in sizes:
            _require(0 <= s <= shape[1],
                     f"size {s} out of range for leaf capacity {shape[1]}")
            if s > 0:
                min_pos = s if min_pos is None else min(min_pos, s)
        total += sum(sizes) * np.dtype(dtype).itemsize
    n = n_blocks or max(
        1, optimal_num_blocks_allgather(p, max(total, 1), model))
    return min(n, max(1, min_pos if min_pos is not None else 1))


def _resolve_quantized(spec: PayloadSpec, p: int, n_blocks: Optional[int],
                       model: CommModel, qblock: int) -> int:
    elems = []
    total = 0
    for shape, dtype in spec.leaves:
        _require(len(shape) >= 1 and shape[0] == p,
                 "payload leaves must have leading axis == axis size "
                 f"(one slice/rank); got {shape} for p={p}")
        _require(np.dtype(dtype) == np.float32,
                 "quantized_allreduce requires float32 leaves (cast, or "
                 "use optim.compression.compressed_allreduce_tree for "
                 f"bf16/f16 gradients); got {np.dtype(dtype).name}")
        e = _leaf_elems(shape[1:])
        elems.append(e)
        total += e  # ~1 wire byte per element (int8 + amortized scales)
    n = n_blocks or max(
        1, optimal_num_blocks_reduce(p, max(total, 1), model))
    # More blocks than ceil(elems/qblock) would be pure padding.
    return min(n, max(1, -(-max(elems) // qblock)))


def _resolve_reduce_scatter(spec: PayloadSpec, p: int,
                            n_blocks: Optional[int],
                            model: CommModel) -> int:
    shards, total = [], 0
    for shape, dtype in spec.leaves:
        _require(len(shape) == 2 and shape[0] == p,
                 f"reduce_scatter leaves must be [p, L]; got {shape}")
        _require(shape[1] % p == 0,
                 f"row length {shape[1]} not divisible by p={p}")
        shards.append(shape[1] // p)
        total += shape[1] * np.dtype(dtype).itemsize
    n = n_blocks or max(1, optimal_num_blocks_allgather(p, total, model))
    return min(n, max(1, max(shards)))


def _is_sizes_leaf(x: Any) -> bool:
    """A per-rank size vector: a flat int sequence or a NumPy array."""
    if isinstance(x, np.ndarray):
        return True
    return isinstance(x, (list, tuple)) and all(
        isinstance(s, (int, np.integer)) for s in x)


def _canon_sizes(spec: PayloadSpec, sizes: Any) -> Tuple[Tuple[int, ...], ...]:
    """Normalize allgatherv sizes: one per-rank list shared by every
    leaf, or a pytree of per-rank lists matching the payload structure."""
    _require(sizes is not None, "allgatherv requires sizes")
    if _is_sizes_leaf(sizes):
        per_leaf = [sizes] * spec.num_leaves
    else:
        treedef = jax.tree.structure(sizes, is_leaf=_is_sizes_leaf)
        _require(
            treedef == spec.treedef,
            f"sizes tree {treedef} does not match payload tree "
            f"{spec.treedef} (pass one per-rank list to share it)")
        per_leaf = jax.tree.leaves(sizes, is_leaf=_is_sizes_leaf)
    return tuple(tuple(int(s) for s in leaf_sizes) for leaf_sizes in per_leaf)


# -------------------------------------------------------------- the comm


@dataclass(frozen=True)
class CirculantComm:
    """Communicator for the circulant collective family on one mesh axis.

    Binds the static context -- mesh, axis, round-step ``backend``
    (``"jnp"`` or ``"pallas"``), alpha-beta cost ``model`` -- once.
    ``plan`` precomputes a :class:`CollectivePlan`; the named collective
    methods are thin plan-cache lookups over it.  Frozen and hashable,
    so communicators themselves are valid cache keys.
    """

    mesh: Mesh
    axis_name: str
    backend: str = "jnp"
    model: CommModel = DEFAULT_MODEL

    def __post_init__(self):
        if self.axis_name not in self.mesh.shape:
            raise ValueError(
                f"axis {self.axis_name!r} not in mesh axes "
                f"{tuple(self.mesh.shape)}")
        if self.backend not in BACKENDS:
            raise ValueError(
                f"unknown round-step backend {self.backend!r} "
                f"(use one of {BACKENDS})")

    @property
    def p(self) -> int:
        return self.mesh.shape[self.axis_name]

    # ------------------------------------------------------------- planning

    def plan(self, kind: str, spec: Any, *, n_blocks: Optional[int] = None,
             root: int = 0, op: str = "sum", sizes: Any = None,
             qblock: Optional[int] = None,
             overlap: bool = False) -> CollectivePlan:
        """Precompute a :class:`CollectivePlan` for ``kind`` and a payload
        spec (an example payload, a pytree of ``ShapeDtypeStruct``s, or a
        :class:`PayloadSpec`).  Cached process-wide: equal arguments
        return the identical plan object.

        ``kind="quantized_allreduce"`` plans the int8-on-the-wire sum
        allreduce (f32 leaves only; ``qblock`` sets the quantization
        block, default :data:`repro.kernels.quant_ops.QBLOCK`); calling
        it returns a ``(sums, errors)`` pair of payload-shaped trees.

        ``overlap=True`` plans the double-buffered executor: each
        round's pack is computed from the pre-update buffer with no data
        dependence on the in-flight exchange, so the round-to-round
        critical path shrinks to exchange -> select -> exchange
        (docs/overlap.md).  Bit-exact vs the sequential executor.
        Supported for broadcast / allgather / allbroadcast / reduce /
        allreduce / reduce_scatter; the irregular ``allgatherv`` and the
        quantized wire (whose requantization is fused into the round
        step) stay sequential.
        """
        if kind not in KINDS:
            raise ValueError(f"unknown collective kind {kind!r} "
                             f"(use one of {KINDS})")
        kind = _CANONICAL_KIND.get(kind, kind)
        _require(not overlap or kind not in ("allgatherv",
                                             "quantized_allreduce"),
                 f"overlap= is not supported for kind {kind!r}")
        spec = payload_spec(spec)
        _require(spec.num_leaves > 0, "payload has no array leaves")
        # Arguments that don't apply to the kind are rejected (a silently
        # dropped op= or root= would return numerically wrong results
        # with no diagnostic), then normalized out of the cache key.
        rooted = kind in ("broadcast", "reduce", "allreduce",
                          "quantized_allreduce")
        reducing = kind in ("reduce", "allreduce")
        _require(rooted or int(root) == 0,
                 f"root= does not apply to kind {kind!r}")
        _require(reducing or op == "sum",
                 f"op= does not apply to kind {kind!r}"
                 + (" (reduce_scatter always sums)"
                    if kind == "reduce_scatter" else "")
                 + (" (quantized_allreduce always sums)"
                    if kind == "quantized_allreduce" else ""))
        _require(kind == "allgatherv" or sizes is None,
                 f"sizes= only applies to allgatherv, not {kind!r}")
        _require(kind == "quantized_allreduce" or qblock is None,
                 f"qblock= only applies to quantized_allreduce, "
                 f"not {kind!r}")
        root_key = int(root) if rooted else 0
        op_key = op if reducing else None
        sizes_key = _canon_sizes(spec, sizes) if kind == "allgatherv" else None
        if kind == "quantized_allreduce":
            from repro.kernels.quant_ops import QBLOCK

            qblock_key: Optional[int] = (QBLOCK if qblock is None
                                         else int(qblock))
            _require(qblock_key >= 1, f"qblock must be >= 1, got {qblock_key}")
        else:
            qblock_key = None
        # Resolve the block count up front (pure host work, also the
        # payload-shape validation) so n_blocks=None and an explicit
        # n_blocks equal to the cost-model optimum key the same entry --
        # one shard_map trace and one XLA executor, not two.
        n = self._resolve_n(kind, spec, n_blocks, sizes_key, qblock_key)
        key = ("commplan", self.mesh, self.axis_name, self.backend,
               self.model, kind, spec, n, root_key, op_key, sizes_key,
               qblock_key, bool(overlap))
        return cached_plan(key, lambda: self._build(
            kind, spec, n, root_key, op_key, sizes_key, qblock_key,
            overlap=bool(overlap)))

    def _resolve_n(self, kind: str, spec: PayloadSpec,
                   n_blocks: Optional[int], sizes_canon,
                   qblock: Optional[int] = None) -> int:
        p = self.p
        if p == 1:
            # The fast path skips payload-shape validation (matching the
            # legacy collectives); sizes lengths ARE still checked, so
            # single-device development catches a wrong-length sizes
            # list before it ships to a real mesh.
            if kind == "allgatherv":
                for sizes in sizes_canon:
                    _require(len(sizes) == p,
                             f"sizes must have length p={p}, "
                             f"got {len(sizes)}")
            return n_blocks or 1
        if kind == "broadcast":
            return _resolve_broadcast(spec, p, n_blocks, self.model,
                                      optimal_num_blocks_bcast)
        if kind == "allgather":
            return _resolve_allgather(spec, p, n_blocks, self.model)
        if kind == "allgatherv":
            return _resolve_allgatherv(spec, p, n_blocks, self.model,
                                       sizes_canon)
        if kind == "reduce_scatter":
            return _resolve_reduce_scatter(spec, p, n_blocks, self.model)
        if kind == "quantized_allreduce":
            return _resolve_quantized(spec, p, n_blocks, self.model, qblock)
        # reduce / allreduce
        return _resolve_broadcast(spec, p, n_blocks, self.model,
                                  optimal_num_blocks_reduce)

    def _build(self, kind: str, spec: PayloadSpec, n: int,
               root: int, op: Optional[str], sizes_canon,
               qblock: Optional[int] = None,
               overlap: bool = False) -> CollectivePlan:
        p = self.p
        if op is not None:
            # Validate the op name host-side, before any tracing; the
            # registry is shared with the kernels so identities agree.
            from repro.kernels.reduce_ops import op_identity

            op_identity(op, np.float32)
        if p == 1:
            # Fast path: nothing moves on a one-rank axis; the plan is
            # the identity.  quantized_allreduce still returns its
            # (sums, errors) pair -- errors identically zero.
            ex = None
            if kind == "quantized_allreduce":
                ex = lambda payload: (  # noqa: E731
                    payload, jax.tree.map(jnp.zeros_like, payload))
            return CollectivePlan(
                kind=kind, spec=spec, p=p, root=0, op=op,
                n_blocks=n, rounds=0, backend=self.backend,
                axis_name=self.axis_name, qblock=qblock, overlap=overlap,
                _execute=ex)

        # The round step rejects payloads it cannot lay out (64-bit
        # leaves or an untileable qblock on the compiled Pallas path)
        # here, at plan time, rather than on the first call.
        step = get_round_step(self.backend)
        for _, dt in spec.leaves:
            step.slot_shape(1, _acc_dtype(dt) if kind == "reduce_scatter"
                            else dt, qblock)
        messages = _plan_messages(kind, spec, p, n, step, qblock,
                                  sizes_canon)
        bundle = get_bundle(p, root)
        mesh, axis = self.mesh, self.axis_name
        if kind == "broadcast":
            ex = _lower_broadcast(mesh, axis, bundle, n, root, self.backend,
                                  spec, overlap=overlap)
            rounds = bundle.rounds(n)
        elif kind == "allgather":
            ex = _lower_allgather(mesh, axis, bundle, n, self.backend, spec,
                                  overlap=overlap)
            rounds = bundle.rounds(n)
        elif kind == "allgatherv":
            ex = _lower_allgatherv(mesh, axis, bundle, n, self.backend, spec,
                                   sizes_canon)
            rounds = bundle.rounds(n)
        elif kind == "reduce_scatter":
            ex = _lower_reduce_scatter(mesh, axis, bundle, n, self.backend,
                                       spec, overlap=overlap)
            rounds = bundle.rounds(n)
        elif kind == "reduce":
            ex = _lower_reduce(mesh, axis, bundle, n, root, op, self.backend,
                               spec, overlap=overlap)
            rounds = bundle.rounds(n)
        elif kind == "quantized_allreduce":
            ex = _lower_quantized_allreduce(mesh, axis, bundle, n, root,
                                            self.backend, spec, qblock)
            rounds = bundle.allreduce_rounds(n)
        else:  # allreduce: reversed reduce then forward broadcast, one n
            red = _lower_reduce(mesh, axis, bundle, n, root, op, self.backend,
                                spec, overlap=overlap)
            bcast = _lower_broadcast(mesh, axis, bundle, n, root,
                                     self.backend, spec, overlap=overlap)
            ex = lambda payload: bcast(red(payload))  # noqa: E731
            rounds = bundle.allreduce_rounds(n)
        return CollectivePlan(
            kind=kind, spec=spec, p=p, root=root, op=op, n_blocks=n,
            rounds=rounds, backend=self.backend, axis_name=self.axis_name,
            qblock=qblock, overlap=overlap,
            permutes=rounds * spec.num_leaves * (
                2 if kind == "quantized_allreduce" else 1),
            wire_bytes=_wire_bytes(kind, messages, rounds),
            tiled_leaves=_tiled_leaves(messages),
            tiled_qslots=_tiled_qslots(kind, messages),
            statics=_plan_statics(kind, bundle, n, axis, overlap=overlap),
            _execute=jax.jit(ex))

    # ------------------------------------------------ collective shorthands
    #
    # Thin plan-cache lookups: spec from the payload, cached plan, call.

    def broadcast(self, x: Any, *, n_blocks: Optional[int] = None,
                  root: int = 0, overlap: bool = False) -> Any:
        """Root's slices reach every rank in ``n-1+ceil(log2 p)`` rounds."""
        return self.plan("broadcast", payload_spec(x), n_blocks=n_blocks,
                         root=root, overlap=overlap)(x)

    def allgather(self, x: Any, *, n_blocks: Optional[int] = None,
                  overlap: bool = False) -> Any:
        """All-to-all broadcast of equal contributions; replicated out."""
        return self.plan("allgather", payload_spec(x), n_blocks=n_blocks,
                         overlap=overlap)(x)

    def allgatherv(self, x: Any, sizes: Any, *,
                   n_blocks: Optional[int] = None) -> Any:
        """Irregular allgather; ``sizes`` is one per-rank list (shared by
        all leaves) or a pytree of per-rank lists matching ``x``."""
        return self.plan("allgatherv", payload_spec(x), n_blocks=n_blocks,
                         sizes=sizes)(x)

    def reduce_scatter(self, x: Any, *, n_blocks: Optional[int] = None,
                       overlap: bool = False) -> Any:
        """Time-reversed all-to-all broadcast: summed shards, scattered."""
        return self.plan("reduce_scatter", payload_spec(x),
                         n_blocks=n_blocks, overlap=overlap)(x)

    def reduce(self, x: Any, *, n_blocks: Optional[int] = None, root: int = 0,
               op: str = "sum", overlap: bool = False) -> Any:
        """Op-reduction to ``root`` on the reversed schedule."""
        return self.plan("reduce", payload_spec(x), n_blocks=n_blocks,
                         root=root, op=op, overlap=overlap)(x)

    def allreduce(self, x: Any, *, n_blocks: Optional[int] = None,
                  root: int = 0, op: str = "sum",
                  overlap: bool = False) -> Any:
        """Reduce + broadcast composition, ``2(n-1)+2*ceil(log2 p)``."""
        return self.plan("allreduce", payload_spec(x), n_blocks=n_blocks,
                         root=root, op=op, overlap=overlap)(x)

    def allbroadcast(self, x: Any, *, n_blocks: Optional[int] = None,
                     overlap: bool = False) -> Any:
        """Family name for the all-to-all broadcast (same plan)."""
        return self.plan("allbroadcast", payload_spec(x),
                         n_blocks=n_blocks, overlap=overlap)(x)

    def quantized_allreduce(self, x: Any, *,
                            n_blocks: Optional[int] = None, root: int = 0,
                            qblock: Optional[int] = None) -> Any:
        """int8-on-the-wire sum allreduce -> ``(sums, errors)`` trees
        (f32 leaves; errors are each rank's local quantization error in
        SUM units -- see docs/gradsync.md)."""
        return self.plan("quantized_allreduce", payload_spec(x),
                         n_blocks=n_blocks, root=root, qblock=qblock)(x)


def get_comm(mesh: Mesh, axis_name: str, *, backend: str = "jnp",
             model: CommModel = DEFAULT_MODEL) -> CirculantComm:
    """The process-cached :class:`CirculantComm` for this context.

    Identity is stable while cached (``get_comm(...) is get_comm(...)``
    for equal arguments), so the legacy ``circulant_*`` shims hit the
    same plan cache as first-class communicator users.
    """
    return cached_plan(
        ("comm", mesh, axis_name, backend, model),
        lambda: CirculantComm(mesh=mesh, axis_name=axis_name,
                              backend=backend, model=model))


# ----------------------------------------------------- host data plans
#
# Single-process executions of the full collectives with the R rows of
# the batched kernels standing in for the p ranks and the network
# exchange realized as a rotation of the rank axis (ppermute's
# r -> (r+s)%p, :func:`_rotate`).  The simulator runs these
# next to its message-passing reference and asserts bit-exact agreement
# -- the certification path for the Pallas backend on CPU CI.  Plans
# are cached like their device siblings: slot tables and the step
# handle are resolved once per (kind, p, n, root, op, backend).


def _as_blocks(values: np.ndarray, lead: int) -> np.ndarray:
    """Normalize payload values to [*lead_shape, n, bs] float/int blocks."""
    arr = np.asarray(values)
    return arr.reshape(arr.shape[: lead + 1] + (-1,)) if arr.ndim > lead + 1 \
        else arr.reshape(arr.shape[: lead + 1] + (1,))


def _rotate(msg, shift: int):
    """The exchange of a host plan: rank r's message moves to rank
    ``(r + shift) % p`` (``ppermute``'s rotation on the leading rank
    axis).  A static row gather rather than ``jnp.roll``: the TPU
    compiler aborts on the roll's concatenation of sublane-unaligned
    row slices of a [p, bs] array (jax 0.9.0 / libtpu, v5e)."""
    p = msg.shape[0]
    return msg[(np.arange(p) - shift) % p]


def _to_slots(vals: np.ndarray, slot: Tuple[int, ...]) -> np.ndarray:
    """[..., bs] host blocks -> [..., *slot], the round step's slot
    layout (zero padded at the tail of each block)."""
    pad = math.prod(slot) - vals.shape[-1]
    vals = np.pad(vals, [(0, 0)] * (vals.ndim - 1) + [(0, pad)])
    return vals.reshape(vals.shape[:-1] + tuple(slot))


def _from_slots(buf, lead: int, bs: int) -> np.ndarray:
    """Inverse of :func:`_to_slots` after ``lead`` leading axes."""
    a = np.asarray(buf)
    return a.reshape(a.shape[:lead] + (-1,))[..., :bs]


@jax.jit
def _jit_requant(x2d):
    """quantize + error capture under jit: one fused multiply-add per
    lane for the error, matching the round-step kernels bit-for-bit."""
    from repro.kernels.quant_ops import quant_blocks, quant_error

    q, sc = quant_blocks(x2d)
    return q, sc, quant_error(x2d, q, sc)


@dataclass(frozen=True, eq=False)
class HostDataPlan:
    """Precomputed host-side data-plane execution (the certification
    harness): slot tables, skip sequence and round-step handle resolved
    at plan time; ``run(values)`` executes only the rounds."""

    kind: str
    p: int
    n: int
    root: int
    op: Optional[str]
    backend: str
    slots: Tuple[np.ndarray, ...] = field(repr=False)
    ks: np.ndarray = field(repr=False)
    skips: Tuple[int, ...] = field(repr=False)
    step: Any = field(repr=False)
    qblock: Optional[int] = None
    overlap: bool = False

    @property
    def statics(self) -> Tuple[PhaseStatic, ...]:
        """Auditable per-phase schedule statics (see
        :mod:`repro.analysis`).  Built from the same process-cached slot
        plans ``run`` executes, so the audited arrays ARE the executed
        ones by identity."""
        return _plan_statics(self.kind, get_bundle(self.p, self.root),
                             self.n, overlap=self.overlap)

    def run(self, values: np.ndarray) -> np.ndarray:
        if self.kind == "broadcast":
            return self._run_broadcast(values)
        if self.kind == "allgather":
            return self._run_allgather(values)
        if self.kind == "quantized_allreduce":
            return self._run_quantized(values)
        return self._run_reduce(values)

    def _run_broadcast(self, values: np.ndarray) -> np.ndarray:
        """``values``: [n] (or [n, bs]) block payloads at the root ->
        final [p, n, bs] data slots of every rank."""
        p, n = self.p, self.n
        recv_slots, send_slots = self.slots
        vals = _as_blocks(values, 0)                 # [n, bs]
        bs = vals.shape[-1]
        slot = self.step.slot_shape(bs, vals.dtype)
        buf = np.zeros((p, n + 1) + slot, vals.dtype)
        buf[self.root, :n] = _to_slots(vals, slot)
        R = len(self.ks)
        with jax.enable_x64(True):
            buf = jnp.asarray(buf)
            msg = self.step.pack(buf, jnp.asarray(send_slots[0]))
            for t in range(R):
                got = _rotate(msg, self.skips[t])
                if t + 1 < R:
                    if self.overlap:
                        pre = self.step.pack(
                            buf, jnp.asarray(send_slots[t + 1]))
                        buf, msg = self.step.shuffle_staged(
                            buf, got, pre, jnp.asarray(recv_slots[t]),
                            jnp.asarray(send_slots[t + 1]))
                    else:
                        buf, msg = self.step.shuffle(
                            buf, got, jnp.asarray(recv_slots[t]),
                            jnp.asarray(send_slots[t + 1]))
                else:
                    buf = self.step.unpack(buf, got,
                                           jnp.asarray(recv_slots[t]))
            return _from_slots(np.asarray(buf)[:, :n], 2, bs)

    def _run_allgather(self, values: np.ndarray) -> np.ndarray:
        """``values``: [p, n(, bs)] per-root payloads -> final
        [p_rank, p_root, n, bs] data slots (rank-major kernel rows)."""
        p, n = self.p, self.n
        (recv_slots,) = self.slots
        vals = _as_blocks(values, 1)                 # [p, n, bs]
        bs = vals.shape[-1]
        slot = self.step.slot_shape(bs, vals.dtype)
        tiled = _to_slots(vals, slot)
        buf = np.zeros((p, p, n + 1) + slot, vals.dtype)
        for j in range(p):
            buf[j, j, :n] = tiled[j]
        base = (np.arange(p)[:, None] - np.arange(p)[None, :]) % p
        R = len(self.ks)

        def slots(t, shift):
            return jnp.asarray(recv_slots[t][(base + shift) % p].reshape(-1))

        with jax.enable_x64(True):
            buf = jnp.asarray(buf.reshape((p * p, n + 1) + slot))
            msg = self.step.pack(buf, slots(0, self.skips[0]))
            for t in range(R):
                sk = self.skips[t]
                got = _rotate(msg.reshape((p, p) + slot),
                              sk).reshape((p * p,) + slot)
                if t + 1 < R:
                    if self.overlap:
                        nxt = slots(t + 1, self.skips[t + 1])
                        pre = self.step.pack(buf, nxt)
                        buf, msg = self.step.shuffle_staged(
                            buf, got, pre, slots(t, 0), nxt)
                    else:
                        buf, msg = self.step.shuffle(
                            buf, got, slots(t, 0),
                            slots(t + 1, self.skips[t + 1]))
                else:
                    buf = self.step.unpack(buf, got, slots(t, 0))
            return _from_slots(
                np.asarray(buf).reshape((p, p, n + 1) + slot)[:, :, :n],
                3, bs)

    def _run_reduce(self, values: np.ndarray) -> np.ndarray:
        """``values``: [p, n(, bs)] per-rank contributions -> final
        [p, n, bs] data slots (row ``root`` holds the op-reduction)."""
        from repro.kernels.reduce_ops import op_identity

        p, n = self.p, self.n
        fwd_slots, acc_slots = self.slots
        vals = _as_blocks(values, 1)                 # [p, n, bs]
        bs = vals.shape[-1]
        slot = self.step.slot_shape(bs, vals.dtype)
        ident = op_identity(self.op, vals.dtype)
        npbuf = np.concatenate(
            [_to_slots(vals, slot),
             np.zeros((p, 1) + slot, vals.dtype),         # garbage slot n
             np.full((p, 1) + slot, ident, vals.dtype)],  # identity n+1
            axis=1)
        R = len(self.ks)
        with jax.enable_x64(True):
            buf = jnp.asarray(npbuf)
            garbage = jnp.full((p,), n, jnp.int32)
            # Initial capture+drain of round 0's forwarded partials (the
            # acc part folds a zero message into the garbage slot).
            buf, msg = self.step.acc_shuffle(
                buf, jnp.zeros((p,) + slot, buf.dtype), garbage,
                jnp.asarray(fwd_slots[0]), op=self.op)
            for t in range(R):
                got = _rotate(msg, -self.skips[t])
                nxt = (jnp.asarray(fwd_slots[t + 1]) if t + 1 < R
                       else garbage)
                if self.overlap:
                    pre = self.step.pack(buf, nxt)
                    buf, msg = self.step.acc_shuffle_staged(
                        buf, got, pre, jnp.asarray(acc_slots[t]), nxt,
                        op=self.op)
                else:
                    buf, msg = self.step.acc_shuffle(
                        buf, got, jnp.asarray(acc_slots[t]), nxt, op=self.op)
            return _from_slots(np.asarray(buf)[:, :n], 2, bs)

    def _run_quantized(self, values: np.ndarray):
        """``values``: [p, n(, bs)] per-rank f32 contributions (bs a
        multiple of qblock) -> ``(out, err)``: the [p, n, bs] lossy sums
        (every row identical) and each rank's locally generated
        quantization error, with ``values.sum(0) == out[r] + err.sum(0)``
        up to f32 accumulation order.  Runs in f32 (the wire format's
        own precision), unlike the exact kinds' x64 certification."""
        from repro.kernels.quant_ops import (
            dequant_blocks,
            quant_blocks,
            quant_error,
        )

        p, n, qb = self.p, self.n, self.qblock
        fwd_slots, acc_slots, recv_slots, send_slots = self.slots
        red_skips, bc_skips = self.skips
        vals = _as_blocks(np.asarray(values, np.float32), 1)  # [p, n, bs]
        bs = vals.shape[-1]
        if bs % qb:
            raise ValueError(f"block size {bs} not a multiple of "
                             f"qblock {qb}")
        slot = self.step.slot_shape(bs, np.float32, qb)
        nb = math.prod(slot) // qb          # quantization blocks per slot
        npbuf = np.concatenate(
            [_to_slots(vals, slot),
             np.zeros((p, 2) + slot, np.float32)], axis=1)  # n: garbage,
        buf = jnp.asarray(npbuf)                            # n+1: identity
        err = jnp.zeros_like(buf)
        garbage = jnp.full((p,), n, jnp.int32)
        buf, err, qm, sm = self.step.qacc_shuffle(
            buf, err, jnp.zeros((p,) + slot, jnp.int8),
            jnp.zeros((p, nb), jnp.float32), garbage,
            jnp.asarray(fwd_slots[0]))
        R = len(red_skips)
        for t in range(R):
            gq = _rotate(qm, -red_skips[t])
            gs = _rotate(sm, -red_skips[t])
            nxt = (jnp.asarray(fwd_slots[t + 1]) if t + 1 < R else garbage)
            buf, err, qm, sm = self.step.qacc_shuffle(
                buf, err, gq, gs, jnp.asarray(acc_slots[t]), nxt)
        # Root-side final requantization: the wire format of the
        # broadcast phase; its error belongs to the root rank.  Jitted
        # so the error capture has the same fused multiply-add rounding
        # as the in-round captures (eager jnp materializes the f32
        # product and rounds twice).
        droot = buf[self.root, :n]                          # [n, *slot]
        q, sc, eps = _jit_requant(droot.reshape(n * nb, qb))
        err = err.at[self.root, :n].add(eps.reshape(droot.shape))
        qbuf = jnp.zeros((p, n + 1) + slot, jnp.int8)
        qbuf = qbuf.at[self.root, :n].set(q.reshape(droot.shape))
        sslot = self.step.slot_shape(nb, np.float32)
        sbuf = jnp.zeros((p, n + 1) + sslot, jnp.float32)
        sbuf = sbuf.at[self.root, :n].set(
            _to_slots(np.asarray(sc).reshape(n, nb), sslot))
        Rb = len(bc_skips)
        msgq = self.step.pack(qbuf, jnp.asarray(send_slots[0]))
        msgs_ = self.step.pack(sbuf, jnp.asarray(send_slots[0]))
        for t in range(Rb):
            gq = _rotate(msgq, bc_skips[t])
            gs = _rotate(msgs_, bc_skips[t])
            if t + 1 < Rb:
                qbuf, msgq = self.step.shuffle(
                    qbuf, gq, jnp.asarray(recv_slots[t]),
                    jnp.asarray(send_slots[t + 1]))
                sbuf, msgs_ = self.step.shuffle(
                    sbuf, gs, jnp.asarray(recv_slots[t]),
                    jnp.asarray(send_slots[t + 1]))
            else:
                qbuf = self.step.unpack(qbuf, gq,
                                        jnp.asarray(recv_slots[t]))
                sbuf = self.step.unpack(sbuf, gs,
                                        jnp.asarray(recv_slots[t]))
        scales = sbuf[:, :n].reshape(p, n, -1)[..., :nb]
        out = dequant_blocks(
            qbuf[:, :n].reshape(p * n * nb, qb),
            scales.reshape(p * n * nb, 1),
        ).reshape(p, n, nb * qb)[..., :bs]
        return np.asarray(out), _from_slots(np.asarray(err)[:, :n], 2, bs)


def host_plan(kind: str, p: int, n: int, *, root: int = 0, op: str = "sum",
              backend: str = "jnp", interpret: Optional[bool] = None,
              qblock: Optional[int] = None,
              overlap: bool = False) -> HostDataPlan:
    """The cached :class:`HostDataPlan` for a certification execution.

    ``kind``: ``"broadcast"``, ``"allgather"``, ``"reduce"`` or
    ``"quantized_allreduce"`` (``qblock`` applies to the latter only).
    ``overlap=True`` runs the double-buffered round loop (unsupported
    for the quantized wire), bit-exact vs the sequential one.  Equal
    arguments return the identical plan object; ``run(values)`` then
    does no schedule or slot-table work.
    """
    if kind not in ("broadcast", "allgather", "reduce",
                    "quantized_allreduce"):
        raise ValueError(f"unknown host data-plane kind {kind!r}")
    if qblock is not None and kind != "quantized_allreduce":
        raise ValueError(f"qblock= does not apply to kind {kind!r}")
    if overlap and kind == "quantized_allreduce":
        raise ValueError("overlap= is not supported for kind "
                         "'quantized_allreduce'")
    if kind == "quantized_allreduce":
        from repro.kernels.quant_ops import QBLOCK

        qblock = QBLOCK if qblock is None else int(qblock)
    root_key = int(root) if kind != "allgather" else 0
    op_key = op if kind in ("reduce", "quantized_allreduce") else None
    if kind == "quantized_allreduce" and op != "sum":
        raise ValueError("quantized_allreduce always sums")
    key = ("hostplan", kind, int(p), int(n), root_key, op_key, backend,
           interpret, qblock, bool(overlap))

    def build():
        bundle = get_bundle(p, root_key)
        if kind == "reduce":
            fwd, acc, ks = reduce_slot_plan(bundle, n)
            slots = (fwd, acc)
            skips = tuple(int(bundle.skip[int(k)]) for k in ks)
        elif kind == "quantized_allreduce":
            fwd, acc, ks = reduce_slot_plan(bundle, n)
            recv, send, ks_b = broadcast_slot_plan(bundle, n)
            slots = (fwd, acc, recv, send)
            # one skip tuple per phase (reduce rounds, broadcast rounds)
            skips = (tuple(int(bundle.skip[int(k)]) for k in ks),
                     tuple(int(bundle.skip[int(k)]) for k in ks_b))
        else:
            recv, send, ks = broadcast_slot_plan(bundle, n)
            slots = (recv, send) if kind == "broadcast" else (recv,)
            skips = tuple(int(bundle.skip[int(k)]) for k in ks)
        return HostDataPlan(
            kind=kind, p=int(p), n=int(n), root=root_key, op=op_key,
            backend=backend, slots=slots, ks=ks, skips=skips,
            step=get_round_step(backend, interpret), qblock=qblock,
            overlap=bool(overlap))

    return cached_plan(key, build)
