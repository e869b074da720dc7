"""Gradient compression with error feedback (distributed-optimization trick).

The lossy mean-allreduce is the quantized circulant allreduce of
:mod:`repro.core.comm` (``2(n-1)+2*ceil(log2 p)`` rounds, the paper's
round-optimal schedule with the wire carrying int8 blocks + per-block
f32 scales and every requantization error captured in the fused round
step), run once per call over the gradient buckets of
:func:`compressed_grad_sync`.

Error-feedback convention (Karimireddy et al. 2019), used everywhere in
this module: **error leaves are f32 and live in SUM units** -- each rank
keeps exactly the quantization error *it generated* (per-hop
requantization + its share of the final quantize), so that

    exact_mean == returned_mean + psum(errors) / p        (completeness)

holds to f32 accumulation tolerance.  Feeding ``g + e`` into the next
mean-allreduce therefore restores the lost mass exactly.  Two historical
bugs made the old accounting first-order wrong:

  * per-hop requantization error was dropped with a comment calling it
    second order -- it is first order and compounds with p (each of the
    p-1 hops requantizes a running partial sum);
  * the final-quantize error was recorded in MEAN units (post ``/p``),
    undercounting the fed-back mass by a factor of p.

Non-finite gradients: quantization flags a block containing NaN/inf via
a NaN scale (see :mod:`repro.kernels.quant_ops`), so the block
dequantizes to all-NaN deterministically on every rank -- visible to
grad-norm guards -- while the error feedback for that block is exactly
zero (never poisoned).

Wire volume for m f32 elements: ~2m int8 bytes (+ scales) versus 8m f32
bytes for an uncompressed allreduce -- a 4x reduction the roofline's
collective term sees directly; the circulant schedule pays
2(n-1)+2*ceil(log2 p) latency terms where a ring pays 2(p-1) (see
docs/gradsync.md for the full table).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.quant_ops import (
    QBLOCK,
    block_nonfinite,
    dequant_blocks,
    quant_blocks,
)

#: Quantization block length (elements sharing one f32 scale).
BLOCK = QBLOCK

__all__ = [
    "BLOCK",
    "quantize_int8",
    "dequantize_int8",
    "block_nonfinite",
    "BucketSpec",
    "make_bucket_spec",
    "bucketize",
    "unbucketize",
    "init_grad_sync_state",
    "compressed_grad_sync",
    "grad_sync_counters",
    "streamed_sync_params",
]


def quantize_int8(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-block symmetric int8 quantization of a [N] f32 vector
    (N % BLOCK == 0) -> (q [nb, BLOCK] int8, scale [nb, 1] f32).

    A block containing any NaN/inf gets a NaN scale (the per-block
    nonfinite flag, see :func:`block_nonfinite`); its finite lanes are
    still quantized against the finite amax, so a single bad lane no
    longer silently poisons the other 255.
    """
    return quant_blocks(x.reshape(-1, BLOCK))


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`quantize_int8` -> flat [N] f32 (flagged blocks
    dequantize to all-NaN deterministically)."""
    return dequant_blocks(q, scale).reshape(-1)


def _cast_with_delta(red: jnp.ndarray, dtype) -> Tuple[jnp.ndarray,
                                                       jnp.ndarray]:
    """Downcast the f32 mean to the gradient dtype, returning the cast
    value and the per-element loss.  Every rank sees the same loss, so
    adding it to each rank's error leaf injects p * delta into the next
    sum -- exactly the delta the next mean needs (sum-unit convention).
    Non-finite deltas (NaN gradients) contribute zero, like
    quant_error."""
    cast = red.astype(dtype)
    if np.dtype(dtype) == np.float32:
        return cast, jnp.zeros_like(red)
    delta = red - cast.astype(jnp.float32)
    return cast, jnp.where(jnp.isfinite(delta), delta, 0.0)


# ----------------------------------------------------- gradient buckets
#
# The trainer syncs gradients per *bucket*, not per leaf: a frozen
# BucketSpec groups leaves greedily (flatten order) into ~bucket_bytes
# f32 buckets, so one quantized-allreduce plan per bucket spec is frozen
# once and reused every step via the process-wide plan cache, and small
# leaves amortize round latency instead of each paying it.


@dataclass(frozen=True)
class BucketSpec:
    """Frozen leaf->bucket assignment for a parameter tree (hashable, so
    it can key plan caches).  ``assignment[i]`` is the bucket of leaf i
    (flatten order), ``offsets[i]`` its element offset inside that
    bucket, ``bucket_sizes[b]`` the total f32 elements of bucket b."""

    leaf_sizes: Tuple[int, ...]
    assignment: Tuple[int, ...]
    offsets: Tuple[int, ...]
    bucket_sizes: Tuple[int, ...]

    @property
    def num_buckets(self) -> int:
        return len(self.bucket_sizes)


def make_bucket_spec(params, bucket_bytes: int = 4 << 20) -> BucketSpec:
    """Greedy bucketization of a pytree's leaves in flatten order.

    ``params`` may hold arrays or ``ShapeDtypeStruct``s.  Buckets are
    filled to ~``bucket_bytes`` of f32 payload (4 bytes/element); a
    leaf larger than the budget gets its own bucket.
    """
    leaves = jax.tree.leaves(params)
    if not leaves:
        raise ValueError("params tree has no array leaves")
    budget = max(1, int(bucket_bytes) // 4)
    sizes, assignment, offsets, bucket_sizes = [], [], [], []
    cur = 0
    for leaf in leaves:
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        if bucket_sizes and cur + n > budget and cur > 0:
            bucket_sizes[-1] = cur
            bucket_sizes.append(0)
            cur = 0
        if not bucket_sizes:
            bucket_sizes.append(0)
        assignment.append(len(bucket_sizes) - 1)
        offsets.append(cur)
        sizes.append(n)
        cur += n
    bucket_sizes[-1] = cur
    return BucketSpec(leaf_sizes=tuple(sizes), assignment=tuple(assignment),
                      offsets=tuple(offsets),
                      bucket_sizes=tuple(bucket_sizes))


def bucketize(tree, spec: BucketSpec) -> List[jnp.ndarray]:
    """Flatten a pytree into ``spec``'s f32 bucket vectors."""
    leaves = jax.tree.leaves(tree)
    if len(leaves) != len(spec.leaf_sizes):
        raise ValueError(f"tree has {len(leaves)} leaves, spec expects "
                         f"{len(spec.leaf_sizes)}")
    parts: List[List[jnp.ndarray]] = [[] for _ in spec.bucket_sizes]
    for leaf, b in zip(leaves, spec.assignment):
        parts[b].append(leaf.astype(jnp.float32).reshape(-1))
    out = []
    for b, chunk in enumerate(parts):
        v = jnp.concatenate(chunk) if len(chunk) > 1 else chunk[0]
        if v.shape[0] != spec.bucket_sizes[b]:
            raise ValueError(f"bucket {b} has {v.shape[0]} elements, "
                             f"spec expects {spec.bucket_sizes[b]}")
        out.append(v)
    return out


def unbucketize(flats: Sequence[jnp.ndarray], spec: BucketSpec, like):
    """Inverse of :func:`bucketize`: slice bucket vectors back into a
    tree shaped (and dtyped) like ``like``.  Returns ``(tree, deltas)``
    where ``deltas`` are per-bucket f32 downcast-loss vectors (zero for
    f32 leaves) for the error-feedback accounting."""
    leaves, treedef = jax.tree.flatten(like)
    outs = []
    deltas = [jnp.zeros((s,), jnp.float32) for s in spec.bucket_sizes]
    for leaf, b, off, n in zip(leaves, spec.assignment, spec.offsets,
                               spec.leaf_sizes):
        sl = jax.lax.dynamic_slice(flats[b], (off,), (n,))
        cast, delta = _cast_with_delta(sl, leaf.dtype)
        outs.append(cast.reshape(leaf.shape))
        deltas[b] = jax.lax.dynamic_update_slice(deltas[b], delta, (off,))
    return treedef.unflatten(outs), deltas


def init_grad_sync_state(spec: BucketSpec, dp: int = 1):
    """Zero error-feedback buckets for :func:`compressed_grad_sync`:
    a tuple of [dp, bucket_size] f32 arrays (leading axis sharded over
    the dp axis by the trainer; ``dp=1`` for unsharded use)."""
    return tuple(jnp.zeros((dp, s), jnp.float32) for s in spec.bucket_sizes)


def compressed_grad_sync(grads, err_buckets, axis_name: str, p: int,
                         spec: BucketSpec, *, backend: str = "jnp",
                         n_blocks: Optional[int] = None,
                         qblock: Optional[int] = None):
    """Bucketized quantized-circulant gradient sync (inside shard_map).

    ``grads``: the local (unreduced) gradient pytree; ``err_buckets``: a
    sequence of flat [bucket_size] f32 error vectors (this rank's rows
    of :func:`init_grad_sync_state`).  All buckets ride ONE quantized
    circulant allreduce call -- one shared schedule, one plan.  Returns
    ``(mean_grads, new_err_buckets)`` with mean_grads in the gradient
    dtypes and errors satisfying the completeness invariant.
    """
    from repro.core import tracing
    from repro.core.comm import circulant_qallreduce_body

    with tracing.scope(tracing.BUCKET):
        flats = bucketize(grads, spec)
        targets = [f + e.reshape(-1) for f, e in zip(flats, err_buckets)]
    sums, errs = circulant_qallreduce_body(
        targets, axis_name, p, n_blocks=n_blocks, backend=backend,
        qblock=qblock)
    with tracing.scope(tracing.BUCKET):
        means = [s / p for s in sums]
        mean_tree, deltas = unbucketize(means, spec, grads)
        new_errs = tuple(e + d for e, d in zip(errs, deltas))
    return mean_tree, new_errs


def grad_sync_counters(spec: BucketSpec, p: int, *, backend: str = "jnp",
                       n_blocks: Optional[int] = None,
                       qblock: Optional[int] = None):
    """Static counters of one :func:`compressed_grad_sync` call over
    ``spec``'s buckets on ``p`` ranks, with the same options: a
    :class:`repro.core.comm.SyncCounters` (block count, rounds,
    permutes, wire bytes a rank sends, and the scales' part of them)."""
    from repro.core.comm import circulant_qallreduce_counters

    return circulant_qallreduce_counters(
        spec.bucket_sizes, p, n_blocks=n_blocks, backend=backend,
        qblock=qblock)


# ------------------------------------------------- streamed bucket sync
#
# The bucket-at-a-time alternative to compressed_grad_sync: instead of
# syncing the fully materialized gradient after the backward completes,
# each parameter bucket is wrapped in a custom_vjp identity whose
# BACKWARD rule runs that bucket's quantized circulant allreduce on the
# incoming cotangent.  Reverse-mode AD reaches a bucket's marker as soon
# as the last layer touching it has been differentiated, so bucket k's
# allreduce enters the graph with no data dependence on the still-
# pending backward of earlier layers -- XLA's scheduler can run the
# collective while that compute proceeds (bucket streaming).  The new
# error-feedback state leaves the backward as the cotangent of the
# error input; gradient accumulation rides in as an explicit ``acc``
# operand (custom_vjp rules must not close over tracers).


def _leaf_meta(leaves) -> Tuple[Tuple[Tuple[int, ...], Any, int], ...]:
    return tuple((tuple(leaf.shape), leaf.dtype,
                  int(np.prod(leaf.shape)) if leaf.shape else 1)
                 for leaf in leaves)


def _make_bucket_sync(meta, axis_name: str, p: int, backend: str,
                      accum_scale: float, n_blocks: Optional[int],
                      qblock: Optional[int]):
    """Build the per-bucket custom_vjp sync marker.

    ``sync(err, acc, *leaves)`` is the identity on ``leaves``; its VJP
    returns ``(new_err, 0, *synced_cts)`` where ``synced_cts`` is the
    lossy mean of ``(acc + cotangents) * accum_scale + err`` across the
    ``axis_name`` ranks and ``new_err`` the updated error-feedback
    bucket (SUM units, downcast deltas folded in)."""

    @jax.custom_vjp
    def sync(err, acc, *leaves):
        return leaves

    def fwd(err, acc, *leaves):
        return leaves, (err, acc)

    def bwd(res, cts):
        err, acc = res
        from repro.core.comm import circulant_qallreduce_body

        parts = [ct.astype(jnp.float32).reshape(-1) for ct in cts]
        flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        target = (acc + flat) * accum_scale + err
        sums, errs = circulant_qallreduce_body(
            [target], axis_name, p, n_blocks=n_blocks, backend=backend,
            qblock=qblock)
        mean = sums[0] / p
        new_err = errs[0].reshape(-1)
        out_cts, off = [], 0
        for shape, dtype, size in meta:
            sl = jax.lax.dynamic_slice(mean, (off,), (size,))
            cast, delta = _cast_with_delta(sl, dtype)
            out_cts.append(cast.reshape(shape))
            new_err = jax.lax.dynamic_update_slice(
                new_err, jax.lax.dynamic_slice(new_err, (off,), (size,))
                + delta, (off,))
            off += size
        return (new_err, jnp.zeros_like(acc)) + tuple(out_cts)

    sync.defvjp(fwd, bwd)
    return sync


def streamed_sync_params(params, err_buckets, acc_buckets,
                         spec: BucketSpec, axis_name: str, p: int, *,
                         backend: str = "jnp", accum_scale: float = 1.0,
                         n_blocks: Optional[int] = None,
                         qblock: Optional[int] = None):
    """Wrap each parameter bucket in a streamed sync marker (inside
    shard_map over ``axis_name``).

    Returns a tree identical to ``params`` in the forward.  Under
    ``jax.value_and_grad(loss, argnums=(params, err_buckets))`` of a
    loss computed THROUGH the returned tree, the params gradient is the
    error-fed lossy mean of ``(acc_buckets + local_grads) * accum_scale``
    -- synced bucket by bucket as the backward produces each bucket's
    cotangent, so bucket k's allreduce overlaps the backward of the
    layers feeding buckets k+1.. -- and the err_buckets gradient is the
    new error-feedback state (the same SUM-unit convention as
    :func:`compressed_grad_sync`).

    ``acc_buckets`` carries previously accumulated raw gradient buckets
    (zeros when there is no accumulation); ``accum_scale`` is the
    microbatch-mean factor applied to ``acc + grad`` before the sync.
    """
    leaves, treedef = jax.tree.flatten(params)
    if len(leaves) != len(spec.leaf_sizes):
        raise ValueError(f"params tree has {len(leaves)} leaves, spec "
                         f"expects {len(spec.leaf_sizes)}")
    if len(err_buckets) != spec.num_buckets:
        raise ValueError(f"{len(err_buckets)} error buckets, spec expects "
                         f"{spec.num_buckets}")
    groups: List[List[Any]] = [[] for _ in spec.bucket_sizes]
    for leaf, b in zip(leaves, spec.assignment):
        groups[b].append(leaf)
    synced: List[List[Any]] = []
    for b, group in enumerate(groups):
        sync = _make_bucket_sync(_leaf_meta(group), axis_name, p, backend,
                                 float(accum_scale), n_blocks, qblock)
        synced.append(list(sync(err_buckets[b].reshape(-1),
                                acc_buckets[b].reshape(-1), *group)))
    # stitch the bucket groups back into flatten order
    out, taken = [], [0] * spec.num_buckets
    for b in spec.assignment:
        out.append(synced[b][taken[b]])
        taken[b] += 1
    return treedef.unflatten(out)
