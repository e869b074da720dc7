"""Legacy per-call entry points for the circulant collective family.

.. deprecated::
    These six ``circulant_*`` functions are compatibility shims over the
    plan/execute communicator API of :mod:`repro.core.comm` -- prefer

        comm = get_comm(mesh, axis_name, backend=..., model=...)
        plan = comm.plan(kind, payload_spec, n_blocks=..., root=..., op=...)
        out = plan(payload)       # or comm.broadcast(x, ...) etc.

    which pulls plan construction (bundle lookup, clamped per-round slot
    tables, round plan, round-step selection, jit executor) out of the
    hot path and generalizes payloads to arbitrary pytrees.  The shims
    resolve the process-cached communicator and plan on every call, so
    they share the compiled executors with first-class plan users -- no
    caller breaks, but each call pays a plan-cache lookup the plan API
    does not.

Semantics (unchanged from the original implementations): each
communication round ``Send(t^k) || Recv(f^k)`` on the circulant graph is
one ``jax.lax.ppermute`` with the static rotation ``r -> (r+skip[k]) %
p``; per-rank slot selection comes from the cached engine bundle's
clamped per-round tables; the per-round pack/exchange/unpack-or-
accumulate step runs through the pluggable
:class:`repro.core.roundstep.RoundStep` backend (``"jnp"`` default,
``"pallas"`` fused kernels).  Round counts are the paper's optima:
``n-1+ceil(log2 p)`` for the forward/reversed single collectives,
``2(n-1)+2*ceil(log2 p)`` for the composed all-reduction.  See
docs/comm.md for the migration table and docs/collectives.md for the
schedule construction.

The seed-era ``CirculantTables`` / ``build_tables`` aliases are kept but
now emit a real :class:`DeprecationWarning` pointing at
:func:`repro.core.engine.get_bundle`.
"""

from __future__ import annotations

import warnings
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .comm import _rot_perm, get_comm
from .costmodel import DEFAULT_MODEL, CommModel
from .engine import ScheduleBundle, get_bundle
# Hierarchical (two-level) one-call entry points live in
# repro.core.hier; re-exported here so the functional collective
# surface stays one import for flat AND hierarchical call sites.
from .hier import (  # noqa: F401  (re-exports)
    hier_allgather,
    hier_allreduce,
    hier_broadcast,
    hier_reduce,
)

__all__ = [
    "circulant_broadcast",
    "circulant_allgather",
    "circulant_allgatherv",
    "circulant_allbroadcast",
    "circulant_reduce",
    "circulant_allreduce",
    "hier_broadcast",
    "hier_reduce",
    "hier_allreduce",
    "hier_allgather",
    "ring_allgather",
    "CirculantTables",
    "build_tables",
]


def CirculantTables(p: int) -> ScheduleBundle:  # noqa: N802 - legacy class name
    """Deprecated alias for :func:`repro.core.engine.get_bundle`."""
    warnings.warn(
        "CirculantTables(p) is deprecated; use repro.core.engine."
        "get_bundle(p, root=0) (same cached ScheduleBundle, rooted tables "
        "included)",
        DeprecationWarning,
        stacklevel=2,
    )
    return get_bundle(p)


def build_tables(p: int) -> ScheduleBundle:
    """Deprecated alias for :func:`repro.core.engine.get_bundle`."""
    warnings.warn(
        "build_tables(p) is deprecated; use repro.core.engine."
        "get_bundle(p, root=0) (same cached ScheduleBundle, rooted tables "
        "included)",
        DeprecationWarning,
        stacklevel=2,
    )
    return get_bundle(p)


# ------------------------------------------------------------------- shims


def circulant_broadcast(
    mesh: Mesh,
    axis_name: str,
    x: jax.Array,
    *,
    n_blocks: Optional[int] = None,
    root: int = 0,
    backend: str = "jnp",
    model: CommModel = DEFAULT_MODEL,
):
    """Round-optimal n-block broadcast of ``x[root]`` along a mesh axis.

    ``x`` has a leading axis of size p sharded over ``axis_name`` (each
    rank owns one slice; only the root's slice content matters).  Returns
    an array of the same spec where every slice equals ``x[root]``.
    Runs in n-1+ceil(log2 p) ppermute rounds (Algorithm 1) -- the
    paper's lower bound for n-block broadcast in the one-ported
    bidirectional model.  Shim over
    :meth:`repro.core.comm.CirculantComm.broadcast`.
    """
    return get_comm(mesh, axis_name, backend=backend, model=model).broadcast(
        x, n_blocks=n_blocks, root=root)


def circulant_allgather(
    mesh: Mesh,
    axis_name: str,
    x: jax.Array,
    *,
    n_blocks: Optional[int] = None,
    backend: str = "jnp",
    model: CommModel = DEFAULT_MODEL,
):
    """All-to-all broadcast (regular allgather) along a mesh axis.

    ``x``: global array sharded on its leading dim over ``axis_name``.
    Returns the fully replicated gathered array (same global shape,
    spec ()) in the optimal n-1+ceil(log2 p) rounds (Algorithm 2 with
    equal contributions).  Shim over
    :meth:`repro.core.comm.CirculantComm.allgather`.
    """
    return get_comm(mesh, axis_name, backend=backend, model=model).allgather(
        x, n_blocks=n_blocks)


def circulant_allgatherv(
    mesh: Mesh,
    axis_name: str,
    x: jax.Array,
    sizes: Sequence[int],
    *,
    n_blocks: Optional[int] = None,
    backend: str = "jnp",
    model: CommModel = DEFAULT_MODEL,
):
    """Irregular allgather (MPI_Allgatherv analogue), Algorithm 2 proper.

    ``x``: [p, cap] sharded over ``axis_name``; rank j's contribution is
    x[j, :sizes[j]] (the rest is padding).  Sizes are static; the wire
    volume tracks sum(sizes), not p*max(sizes) (paper Figure 2's
    degenerate case).  Returns the replicated [p, cap] array with row j
    = rank j's data.  Shim over
    :meth:`repro.core.comm.CirculantComm.allgatherv`.

    Block sizes are ragged per root, so the data plane uses the
    round-step ``pack``/``unpack`` primitives per root row; with
    ``backend="pallas"`` that means 2p single-row kernel launches per
    round -- correct and tested, but prefer ``"jnp"`` for ragged sizes.
    """
    return get_comm(mesh, axis_name, backend=backend, model=model).allgatherv(
        x, sizes, n_blocks=n_blocks)


def circulant_reduce_scatter(
    mesh: Mesh,
    axis_name: str,
    x: jax.Array,
    *,
    n_blocks: Optional[int] = None,
    backend: str = "jnp",
    model: CommModel = DEFAULT_MODEL,
):
    """BEYOND-PAPER: round-optimal reduce-scatter by *time reversal* of the
    circulant all-to-all broadcast (allgather and reduce-scatter are dual
    collectives; reversing every round of Algorithm 2 -- negated
    rotations, send-what-you-received, accumulate-what-you-sent -- yields
    an n-1+ceil(log2 p)-round reduce-scatter on the same schedules).

    ``x``: [p, L] sharded on dim 0 over ``axis_name``; row r is rank r's
    full L-length contribution with L = p * shard.  Returns [p, shard]
    sharded the same way: row r = sum_r' x[r'] restricted to shard r.
    Shim over :meth:`repro.core.comm.CirculantComm.reduce_scatter`.
    """
    return get_comm(mesh, axis_name, backend=backend,
                    model=model).reduce_scatter(x, n_blocks=n_blocks)


def circulant_reduce(
    mesh: Mesh,
    axis_name: str,
    x: jax.Array,
    *,
    n_blocks: Optional[int] = None,
    root: int = 0,
    op: str = "sum",
    backend: str = "jnp",
    model: CommModel = DEFAULT_MODEL,
):
    """Round-optimal n-block reduction to ``root`` (reversed Algorithm 1).

    ``x`` has a leading axis of size p sharded over ``axis_name``.
    Returns an array of the same spec where the root's slice is the
    elementwise op-reduction (``"sum"`` or ``"max"``, exact by the
    capture-drain-accumulate rule) of all slices and every other slice
    is zero, in the optimal ``n-1+ceil(log2 p)`` rounds
    (arXiv:2407.18004 time reversal).  Shim over
    :meth:`repro.core.comm.CirculantComm.reduce`.
    """
    return get_comm(mesh, axis_name, backend=backend, model=model).reduce(
        x, n_blocks=n_blocks, root=root, op=op)


def circulant_allreduce(
    mesh: Mesh,
    axis_name: str,
    x: jax.Array,
    *,
    n_blocks: Optional[int] = None,
    root: int = 0,
    op: str = "sum",
    backend: str = "jnp",
    model: CommModel = DEFAULT_MODEL,
):
    """All-reduction in the composed ``2(n-1)+2*ceil(log2 p)`` rounds.

    Reduce to ``root`` on the reversed schedule, then broadcast the
    result back on the forward schedule -- both phases on the same
    cached bundle and block count.  Every output slice equals the
    elementwise op-reduction of all input slices.  Shim over
    :meth:`repro.core.comm.CirculantComm.allreduce`.
    """
    return get_comm(mesh, axis_name, backend=backend, model=model).allreduce(
        x, n_blocks=n_blocks, root=root, op=op)


def circulant_allbroadcast(
    mesh: Mesh,
    axis_name: str,
    x: jax.Array,
    *,
    n_blocks: Optional[int] = None,
    backend: str = "jnp",
    model: CommModel = DEFAULT_MODEL,
):
    """All-broadcast: every rank's slice reaches every rank in the
    optimal ``n-1+ceil(log2 p)`` rounds.

    The collective-family name (arXiv:2407.18004) for the all-to-all
    broadcast of Algorithm 2; identical to :func:`circulant_allgather`.
    Shim over :meth:`repro.core.comm.CirculantComm.allbroadcast`.
    """
    return get_comm(mesh, axis_name, backend=backend,
                    model=model).allbroadcast(x, n_blocks=n_blocks)


# ----------------------------------------------------------- ring baseline


def ring_allgather(mesh: Mesh, axis_name: str, x: jax.Array):
    """Classic p-1 round ring allgather baseline (bandwidth-optimal,
    latency p-1 rounds vs the circulant's n-1+ceil(log2 p))."""
    p = mesh.shape[axis_name]
    if p == 1:
        return x

    def body(xs):
        r = jax.lax.axis_index(axis_name)
        parts = [(r, xs)]
        cur = xs
        for _ in range(p - 1):
            cur = jax.lax.ppermute(cur, axis_name, _rot_perm(p, 1))
            parts.append((None, cur))
        # piece i came from rank (r - i) % p; place rows by origin
        buf = jnp.zeros((p,) + xs.shape, xs.dtype)
        cur = xs
        buf = jax.lax.dynamic_update_slice(buf, xs[None], (r,) + (0,) * xs.ndim)
        for i in range(1, p):
            cur = parts[i][1]
            src = (r - i) % p
            buf = jax.lax.dynamic_update_slice(buf, cur[None], (src,) + (0,) * xs.ndim)
        return buf.reshape((p * xs.shape[0],) + xs.shape[1:])

    shard = jax.shard_map(
        body, mesh=mesh, in_specs=P(axis_name), out_specs=P(), check_vma=False
    )
    return shard(x)
