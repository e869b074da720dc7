"""Two-level hierarchical circulant collectives (the paper's 36x32 topology).

The paper evaluates its round-optimal broadcast on a 36-node x 32-core
cluster, where the intra-node and inter-node link costs differ by an
order of magnitude.  A flat circulant schedule over p = nodes*cores
prices every hop identically; the classic remedy -- and the one the
collective family of arXiv:2407.18004 composes naturally into -- is a
*hierarchical* two-level decomposition, one circulant collective per
level:

  * ``broadcast``: inter-node circulant broadcast among the node
    leaders (the ``root``'s core row), then an intra-node broadcast
    inside every node;
  * ``reduce`` (the dual): intra-node reduction to each node's leader,
    then inter-node reduction of the leader partials to the root;
  * ``allreduce``: intra-reduce -> inter-allreduce among leaders ->
    intra-broadcast fan-out, 2(n_C-1+q_C) + 2(n_N-1+q_N) rounds;
  * ``allgather``: leader gather + circulant exchange + local fan-out,
    realized as the equivalent two-phase all-to-all broadcast (the
    intra phase *is* the fused gather+fan-out) -- intra allgather of
    the core contributions, then inter allgather of the node blocks.

Each level gets its **own** artifacts from the process-wide engine
caches -- :func:`repro.core.engine.get_bundle` for the schedule tables,
the clamped slot plans of :mod:`repro.core.roundstep`, the shared
:class:`~repro.core.roundstep.RoundStep` backend handle -- and its own
block count from a per-level :class:`~repro.core.costmodel.CommModel`
(:func:`repro.core.costmodel.optimal_hier_blocks`).  The two phases run
inside ONE ``shard_map`` body over the 2D mesh: level-1 rounds are
``ppermute``\\ s along ``inter_axis``, level-2 rounds along
``intra_axis``, with a host-side re-blocking between them.  Payloads
are arbitrary pytrees with the same leaf packing as
:mod:`repro.core.comm` (per-leaf block split, one shared schedule per
tree per level).

Flat ranks are node-major: rank ``r = node * cores + core``; a payload
leaf's leading axis is the flat rank axis, sharded over
``P((inter_axis, intra_axis))``.  Degenerate meshes compose away: a
``1 x p`` mesh runs only the intra level (== the flat collective) and a
``p x 1`` mesh only the inter level.

The module also hosts the hierarchical **host data plane**
(:class:`HierHostPlan` / :func:`hier_host_plan`): single-process
executions composing the cached per-level host plans of
:mod:`repro.core.comm`, which :func:`repro.core.simulator.
simulate_hier_broadcast` (and friends) assert bit-exact against the
message-passing reference -- the certification path for both round-step
backends on CPU CI, including the full 36x32 grid.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from . import tracing
from .costmodel import DEFAULT_MODEL, CommModel, optimal_hier_blocks
from .engine import cached_plan, get_bundle
from .roundstep import (
    BACKENDS,
    PhaseStatic,
    allgather_phase_static,
    broadcast_phase_static,
    broadcast_slot_plan,
    get_round_step,
    reduce_phase_static,
    reduce_slot_plan,
)
from .schedule import num_rounds
from .comm import (
    PayloadSpec,
    _allgather_phase,
    _bcast_phase,
    _leaf_elems,
    _message_slot,
    _reduce_phase,
    _require,
    _rot_perm,
    _tree_executor,
    host_plan,
    payload_spec,
    validate_payload,
)
from .tracing import scope, span

__all__ = [
    "HIER_KINDS",
    "hier_rounds",
    "HierPlan",
    "HierComm",
    "get_hier_comm",
    "hier_broadcast",
    "hier_reduce",
    "hier_allreduce",
    "hier_allgather",
    "HierHostPlan",
    "hier_host_plan",
]

#: Collective kinds the hierarchical layer composes.  ``"allbroadcast"``
#: is the family alias and canonicalizes onto ``"allgather"``.
HIER_KINDS = ("broadcast", "reduce", "allreduce", "allgather", "allbroadcast")

_CANONICAL_KIND = {"allbroadcast": "allgather"}


def hier_rounds(kind: str, nodes: int, cores: int,
                n_inter: int, n_intra: int) -> int:
    """Composed closed-form round count of a two-level collective.

    Each level contributes its flat optimum (``n-1+ceil(log2 p)``, 0 on
    a one-rank level); broadcast / reduce / allgather run one phase per
    level, the all-reduction runs both directions at both levels:
    ``2(n_C-1+q_C) + 2(n_N-1+q_N)``.
    """
    kind = _CANONICAL_KIND.get(kind, kind)
    if kind not in ("broadcast", "reduce", "allreduce", "allgather"):
        raise ValueError(f"unknown hier kind {kind!r} "
                         f"(use one of {HIER_KINDS})")
    per_level = num_rounds(nodes, n_inter) + num_rounds(cores, n_intra)
    return 2 * per_level if kind == "allreduce" else per_level


# -------------------------------------------------------- device lowerings
#
# The per-axis phase bodies (_bcast_phase / _reduce_phase /
# _allgather_phase) are the SAME helpers the flat lowerings in
# repro.core.comm wrap -- one copy of each round loop serves both
# layers.  Here two phases chain along different mesh axes inside one
# shard_map body, with the host-side flatten/split re-blocking seam
# between them.


def _level_plans(bundle, n, kind):
    """(slot arrays, ks) for one level from the process-wide plan cache."""
    if kind == "reduce":
        fwd, acc, ks = reduce_slot_plan(bundle, n)
        return (fwd, acc), ks
    recv, send, ks = broadcast_slot_plan(bundle, n)
    return (recv, send), ks


def _fwd_perms(bundle, ks):
    return [_rot_perm(bundle.p, bundle.skip[int(k)]) for k in ks]


def _rev_perms(bundle, ks):
    return [_rot_perm(bundle.p, (bundle.p - bundle.skip[int(k)]) % bundle.p)
            for k in ks]


def _lower_hier(mesh: Mesh, inter_axis: str, intra_axis: str, kind: str,
                bN, bC, nN: int, nC: int, rootN: int, rootC: int,
                op: Optional[str], backend: str,
                spec: PayloadSpec) -> Callable:
    """One shard_map body running the composed per-level phases.

    Level-1 rounds ppermute along ``inter_axis`` (all core rows run them
    in lockstep; only the leader row's data is meaningful), level-2
    rounds along ``intra_axis``.  Correctness Condition 4 guarantees no
    rank ever forwards a data slot it has not received, so the inactive
    rows cannot pollute the final state -- their buffers are overwritten
    (broadcast) or drained to the op identity (reduce) phase by phase.
    """
    N, C = bN.p, bC.p
    step = get_round_step(backend)
    L = spec.num_leaves

    # Per-level static artifacts, each from the spec-keyed engine cache:
    # (slots, perms, skips) per forward level, (slots, perms) reversed.
    # Forward (broadcast-direction) phases run for every kind but reduce.
    inter = intra = None
    if kind != "reduce":
        if N > 1:
            slots, ks = _level_plans(bN, nN, "broadcast")
            inter = (slots, _fwd_perms(bN, ks),
                     [int(bN.skip[int(k)]) for k in ks])
        if C > 1:
            slots, ks = _level_plans(bC, nC, "broadcast")
            intra = (slots, _fwd_perms(bC, ks),
                     [int(bC.skip[int(k)]) for k in ks])
    rinter = rintra = None
    if kind in ("reduce", "allreduce"):
        if N > 1:
            slots, ks = _level_plans(bN, nN, "reduce")
            rinter = (slots, _rev_perms(bN, ks))
        if C > 1:
            slots, ks = _level_plans(bC, nC, "reduce")
            rintra = (slots, _rev_perms(bC, ks))

    if op is not None:
        from repro.kernels.reduce_ops import op_identity

        idents = [op_identity(op, dt) for _, dt in spec.leaves]

    def body(*shards):
        node = jax.lax.axis_index(inter_axis)
        core = jax.lax.axis_index(intra_axis)
        is_root = (node == rootN) & (core == rootC)
        shapes = [xs.shape for xs in shards]
        with scope(tracing.SPLIT):
            flats = [xs.reshape(-1) for xs in shards]
            if kind == "broadcast":
                flats = [jnp.where(is_root, f, jnp.zeros_like(f))
                         for f in flats]

        if kind == "broadcast":
            if inter is not None:   # leaders: broadcast across nodes
                (recv, send), perms, _ = inter
                flats = _bcast_phase(flats, nN, recv, send, perms,
                                     inter_axis, node, step)
            if intra is not None:   # fan-out inside every node
                (recv, send), perms, _ = intra
                flats = _bcast_phase(flats, nC, recv, send, perms,
                                     intra_axis, core, step)
            with scope(tracing.JOIN):
                return tuple(f.reshape(shape) for f, shape in
                             zip(flats, shapes))

        if kind == "reduce":
            if rintra is not None:  # each node reduces to its leader
                (fwd, acc), perms = rintra
                flats = _reduce_phase(flats, nC, fwd, acc, perms,
                                      intra_axis, core, idents, op, step)
            if rinter is not None:  # leaders reduce to the root
                (fwd, acc), perms = rinter
                flats = _reduce_phase(flats, nN, fwd, acc, perms,
                                      inter_axis, node, idents, op, step)
            with scope(tracing.JOIN):
                return tuple(
                    jnp.where(is_root, f, jnp.zeros_like(f)).reshape(shape)
                    for f, shape in zip(flats, shapes))

        if kind == "allreduce":
            if rintra is not None:
                (fwd, acc), perms = rintra
                flats = _reduce_phase(flats, nC, fwd, acc, perms,
                                      intra_axis, core, idents, op, step)
            if rinter is not None:
                (fwd, acc), perms = rinter
                flats = _reduce_phase(flats, nN, fwd, acc, perms,
                                      inter_axis, node, idents, op, step)
            if inter is not None:   # leaders: broadcast the result back
                (recv, send), perms, _ = inter
                flats = _bcast_phase(flats, nN, recv, send, perms,
                                     inter_axis, node, step)
            if intra is not None:
                (recv, send), perms, _ = intra
                flats = _bcast_phase(flats, nC, recv, send, perms,
                                     intra_axis, core, step)
            with scope(tracing.JOIN):
                return tuple(f.reshape(shape) for f, shape in
                             zip(flats, shapes))

        # allgather: intra phase (fused leader-gather + fan-out), then
        # inter exchange of the node blocks -- rank-major output.
        if intra is not None:
            (recv, _), perms, skips = intra
            flats = _allgather_phase(flats, nC, recv, skips, perms,
                                     intra_axis, core, C, step)
        if inter is not None:
            (recv, _), perms, skips = inter
            flats = _allgather_phase(flats, nN, recv, skips, perms,
                                     inter_axis, node, N, step)
        with scope(tracing.JOIN):
            return tuple(
                f.reshape((N * C * shape[0],) + tuple(shape[1:]))
                for f, shape in zip(flats, shapes))

    replicated_out = kind == "allgather"
    shard_fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P((inter_axis, intra_axis)),) * L,
        out_specs=((P(),) if replicated_out
                   else (P((inter_axis, intra_axis)),)) * L,
        # jax has no replication rule for pallas_call inside shard_map,
        # and the allgather result is replicated by construction.
        check_vma=(backend == "jnp") and not replicated_out,
    )

    return _tree_executor(shard_fn, spec.treedef)


def _hier_statics(kind: str, bN, bC, nN: int, nC: int, inter_axis: str,
                  intra_axis: str) -> Tuple[PhaseStatic, ...]:
    """Per-phase audit records of a two-level collective, in the exact
    execution order of :func:`_lower_hier` (one-rank levels compose
    away).  Each record's tables come from the same process-cached slot
    plans the lowering closed over."""
    N, C = bN.p, bC.p
    inter_b = ((broadcast_phase_static(bN, nN, axis=inter_axis),)
               if N > 1 else ())
    intra_b = ((broadcast_phase_static(bC, nC, axis=intra_axis),)
               if C > 1 else ())
    inter_r = ((reduce_phase_static(bN, nN, axis=inter_axis),)
               if N > 1 else ())
    intra_r = ((reduce_phase_static(bC, nC, axis=intra_axis),)
               if C > 1 else ())
    if kind == "broadcast":
        return inter_b + intra_b
    if kind == "reduce":
        return intra_r + inter_r
    if kind == "allreduce":
        return intra_r + inter_r + inter_b + intra_b
    # allgather: intra phase then inter exchange of the node blocks
    inter_g = ((allgather_phase_static(bN, nN, axis=inter_axis),)
               if N > 1 else ())
    intra_g = ((allgather_phase_static(bC, nC, axis=intra_axis),)
               if C > 1 else ())
    return intra_g + inter_g


# ------------------------------------------------------------ plan objects


@dataclass(frozen=True, eq=False)
class HierPlan:
    """A fully precomputed two-level collective: call it with payloads.

    Mirrors :class:`repro.core.comm.CollectivePlan`: every static
    artifact (both level bundles, both clamped slot-table sets, the
    per-round rotations, the round-step handle, the jit executor) was
    resolved at plan time; ``plan(payload)`` validates the payload and
    dispatches the compiled rounds.  Cached process-wide -- equal specs
    return the identical object.
    """

    kind: str
    spec: PayloadSpec
    nodes: int
    cores: int
    root: int
    op: Optional[str]
    n_inter: int
    n_intra: int
    rounds: int
    rounds_inter: int
    rounds_intra: int
    backend: str
    inter_axis: str
    intra_axis: str
    #: ``ppermute``s one call issues (one per leaf per round), the bytes
    #: one rank sends in a call, and the payload leaves whose slots the
    #: round step lays out as tile stacks; static, counted at plan time.
    permutes: int = 0
    wire_bytes: int = 0
    tiled_leaves: int = 0
    #: Auditable per-phase schedule statics in execution order (see
    #: repro.analysis.planaudit); () on the p == 1 fast path.
    statics: Tuple[PhaseStatic, ...] = field(repr=False, default=())
    _execute: Optional[Callable] = field(repr=False, default=None)

    @property
    def p(self) -> int:
        return self.nodes * self.cores

    def __call__(self, payload: Any) -> Any:
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
        return (f"hier-{self.kind} mesh={self.nodes}x{self.cores} "
                f"root={self.root} n=({self.n_inter},{self.n_intra}) "
                f"rounds={self.rounds} (inter {self.rounds_inter} + intra "
                f"{self.rounds_intra}) permutes={self.permutes} "
                f"wire_bytes={self.wire_bytes} "
                f"tiled_leaves={self.tiled_leaves} "
                f"backend={self.backend}{extra} "
                f"spec={self.spec.describe()}")


# --------------------------------------------------------- n-block choice


def _resolve_hier_blocks(kind: str, spec: PayloadSpec, nodes: int, cores: int,
                         n_inter: Optional[int], n_intra: Optional[int],
                         inter_model: CommModel,
                         intra_model: CommModel) -> Tuple[int, int]:
    p = nodes * cores
    elems, total = [], 0
    for shape, dtype in spec.leaves:
        if kind == "allgather":
            _require(len(shape) >= 1 and shape[0] % p == 0,
                     f"leading dim {shape[0] if shape else 0} not divisible "
                     f"by mesh size {nodes}x{cores}={p}")
            e = (shape[0] // p) * _leaf_elems(shape[1:])
        else:
            _require(len(shape) >= 1 and shape[0] == p,
                     "payload leaves must have leading axis == nodes*cores "
                     f"(one slice/rank); got {shape} for {nodes}x{cores}")
            e = _leaf_elems(shape[1:])
        elems.append(e)
        total += e * np.dtype(dtype).itemsize
    if kind == "allgather":
        # Inter level exchanges node blocks (the full p*e payload);
        # intra only the node's share.
        m_inter, m_intra = total * p, total * cores
    else:
        m_inter = m_intra = total
    auto_n, auto_c = optimal_hier_blocks(nodes, cores, m_inter, m_intra,
                                         inter_model, intra_model, kind=kind)
    cap = max(1, max(elems))
    if kind == "allgather":
        cap_intra = cap              # per-rank contribution elems
        cap_inter = cap * cores      # node-block elems
    else:
        cap_intra = cap_inter = cap
    nN = min(max(1, n_inter or auto_n), cap_inter)
    nC = min(max(1, n_intra or auto_c), cap_intra)
    return nN, nC


# ---------------------------------------------------------------- the comm


@dataclass(frozen=True)
class HierComm:
    """Two-level hierarchical communicator over a (nodes x cores) mesh.

    Binds the static context once: the 2D ``mesh``, the ``inter_axis``
    (nodes) and ``intra_axis`` (cores) names, the round-step
    ``backend``, and one :class:`~repro.core.costmodel.CommModel` per
    level (the whole point of going hierarchical: the inter-node links
    are priced differently from the intra-node ones).  ``plan``
    precomputes a :class:`HierPlan`; the named collectives are thin
    plan-cache lookups.  Frozen and hashable.
    """

    mesh: Mesh
    inter_axis: str
    intra_axis: str
    backend: str = "jnp"
    inter_model: CommModel = DEFAULT_MODEL
    intra_model: CommModel = DEFAULT_MODEL

    def __post_init__(self):
        for axis in (self.inter_axis, self.intra_axis):
            if axis not in self.mesh.shape:
                raise ValueError(f"axis {axis!r} not in mesh axes "
                                 f"{tuple(self.mesh.shape)}")
        if self.inter_axis == self.intra_axis:
            raise ValueError("inter_axis and intra_axis must differ, got "
                             f"{self.inter_axis!r} twice")
        if self.backend not in BACKENDS:
            raise ValueError(f"unknown round-step backend {self.backend!r} "
                             f"(use one of {BACKENDS})")

    @property
    def nodes(self) -> int:
        return self.mesh.shape[self.inter_axis]

    @property
    def cores(self) -> int:
        return self.mesh.shape[self.intra_axis]

    @property
    def p(self) -> int:
        return self.nodes * self.cores

    # ------------------------------------------------------------- planning

    def plan(self, kind: str, spec: Any, *,
             n_inter: Optional[int] = None, n_intra: Optional[int] = None,
             root: int = 0, op: str = "sum") -> HierPlan:
        """Precompute a :class:`HierPlan` for ``kind`` and a payload spec.

        ``root`` is the flat node-major rank ``node * cores + core``.
        ``n_inter`` / ``n_intra`` override the per-level cost-model
        optima.  Cached process-wide; equal arguments return the
        identical plan object.
        """
        if kind not in HIER_KINDS:
            raise ValueError(f"unknown hier kind {kind!r} "
                             f"(use one of {HIER_KINDS})")
        kind = _CANONICAL_KIND.get(kind, kind)
        spec = payload_spec(spec)
        _require(spec.num_leaves > 0, "payload has no array leaves")
        rooted = kind in ("broadcast", "reduce", "allreduce")
        reducing = kind in ("reduce", "allreduce")
        _require(rooted or int(root) == 0,
                 f"root= does not apply to hier kind {kind!r}")
        _require(reducing or op == "sum",
                 f"op= does not apply to hier kind {kind!r}")
        _require(0 <= int(root) < self.p,
                 f"root must be in [0, nodes*cores), got {root} for "
                 f"{self.nodes}x{self.cores}")
        root_key = int(root) if rooted else 0
        op_key = op if reducing else None
        nN, nC = self._resolve_n(kind, spec, n_inter, n_intra)
        key = ("hierplan", self.mesh, self.inter_axis, self.intra_axis,
               self.backend, self.inter_model, self.intra_model, kind, spec,
               nN, nC, root_key, op_key)
        return cached_plan(key, lambda: self._build(
            kind, spec, nN, nC, root_key, op_key))

    def _resolve_n(self, kind: str, spec: PayloadSpec,
                   n_inter: Optional[int],
                   n_intra: Optional[int]) -> Tuple[int, int]:
        if self.p == 1:
            return max(1, n_inter or 1), max(1, n_intra or 1)
        return _resolve_hier_blocks(kind, spec, self.nodes, self.cores,
                                    n_inter, n_intra, self.inter_model,
                                    self.intra_model)

    def _build(self, kind: str, spec: PayloadSpec, nN: int, nC: int,
               root: int, op: Optional[str]) -> HierPlan:
        nodes, cores = self.nodes, self.cores
        if op is not None:
            from repro.kernels.reduce_ops import op_identity

            op_identity(op, np.float32)  # host-side op validation
        rN = num_rounds(nodes, nN)
        rC = num_rounds(cores, nC)
        scale = 2 if kind == "allreduce" else 1
        common = dict(kind=kind, spec=spec, nodes=nodes, cores=cores,
                      root=root, op=op, n_inter=nN, n_intra=nC,
                      rounds=scale * (rN + rC), rounds_inter=scale * rN,
                      rounds_intra=scale * rC, backend=self.backend,
                      inter_axis=self.inter_axis, intra_axis=self.intra_axis)
        if self.p == 1:
            return HierPlan(_execute=None, **common)
        step = get_round_step(self.backend)
        wire = tiled = 0
        for shape, dtype in spec.leaves:
            # (rounds, elements, blocks, rows) of each level's messages
            if kind == "allgather":
                # intra rounds carry one slot per core row, inter rounds
                # one per node row of the node blocks
                e = shape[0] // self.p * _leaf_elems(shape[1:])
                levels = ((rC, e, nC, cores), (rN, cores * e, nN, nodes))
            else:
                e = _leaf_elems(shape[1:])
                levels = ((scale * rN, e, nN, 1), (scale * rC, e, nC, 1))
            leaf_tiled = False
            for rounds, elems, nb, rows in levels:
                if rounds:
                    slot = _message_slot(step, elems, dtype, nb)
                    wire += (rounds * rows * math.prod(slot)
                             * np.dtype(dtype).itemsize)
                    leaf_tiled |= len(slot) > 1
            tiled += leaf_tiled
        rootN, rootC = divmod(root, cores)
        bN = get_bundle(nodes, rootN)
        bC = get_bundle(cores, rootC)
        ex = _lower_hier(self.mesh, self.inter_axis, self.intra_axis, kind,
                         bN, bC, nN, nC, rootN, rootC, op, self.backend, spec)
        return HierPlan(_execute=jax.jit(ex),
                        permutes=common["rounds"] * spec.num_leaves,
                        wire_bytes=wire,
                        tiled_leaves=tiled,
                        statics=_hier_statics(kind, bN, bC, nN, nC,
                                              self.inter_axis,
                                              self.intra_axis),
                        **common)

    # ------------------------------------------------ collective shorthands

    def broadcast(self, x: Any, *, n_inter: Optional[int] = None,
                  n_intra: Optional[int] = None, root: int = 0) -> Any:
        """Leader broadcast + intra fan-out of flat rank ``root``'s slices."""
        return self.plan("broadcast", payload_spec(x), n_inter=n_inter,
                         n_intra=n_intra, root=root)(x)

    def reduce(self, x: Any, *, n_inter: Optional[int] = None,
               n_intra: Optional[int] = None, root: int = 0,
               op: str = "sum") -> Any:
        """Intra-reduce to the leaders, then inter-reduce to ``root``."""
        return self.plan("reduce", payload_spec(x), n_inter=n_inter,
                         n_intra=n_intra, root=root, op=op)(x)

    def allreduce(self, x: Any, *, n_inter: Optional[int] = None,
                  n_intra: Optional[int] = None, root: int = 0,
                  op: str = "sum") -> Any:
        """Intra-reduce -> inter-allreduce -> intra-broadcast fan-out."""
        return self.plan("allreduce", payload_spec(x), n_inter=n_inter,
                         n_intra=n_intra, root=root, op=op)(x)

    def allgather(self, x: Any, *, n_inter: Optional[int] = None,
                  n_intra: Optional[int] = None) -> Any:
        """Two-phase all-to-all broadcast; replicated rank-major result."""
        return self.plan("allgather", payload_spec(x), n_inter=n_inter,
                         n_intra=n_intra)(x)


def get_hier_comm(mesh: Mesh, inter_axis: str, intra_axis: str, *,
                  backend: str = "jnp",
                  inter_model: CommModel = DEFAULT_MODEL,
                  intra_model: CommModel = DEFAULT_MODEL) -> HierComm:
    """The process-cached :class:`HierComm` for this context (identity is
    stable while cached, like :func:`repro.core.comm.get_comm`)."""
    return cached_plan(
        ("hiercomm", mesh, inter_axis, intra_axis, backend, inter_model,
         intra_model),
        lambda: HierComm(mesh=mesh, inter_axis=inter_axis,
                         intra_axis=intra_axis, backend=backend,
                         inter_model=inter_model, intra_model=intra_model))


# ------------------------------------------------------ functional wrappers


def hier_broadcast(mesh: Mesh, inter_axis: str, intra_axis: str, x: Any, *,
                   n_inter: Optional[int] = None,
                   n_intra: Optional[int] = None, root: int = 0,
                   backend: str = "jnp") -> Any:
    """One-call hierarchical broadcast (plan-cache lookup under the hood)."""
    return get_hier_comm(mesh, inter_axis, intra_axis,
                         backend=backend).broadcast(
        x, n_inter=n_inter, n_intra=n_intra, root=root)


def hier_reduce(mesh: Mesh, inter_axis: str, intra_axis: str, x: Any, *,
                n_inter: Optional[int] = None, n_intra: Optional[int] = None,
                root: int = 0, op: str = "sum", backend: str = "jnp") -> Any:
    """One-call hierarchical reduction to flat rank ``root``."""
    return get_hier_comm(mesh, inter_axis, intra_axis,
                         backend=backend).reduce(
        x, n_inter=n_inter, n_intra=n_intra, root=root, op=op)


def hier_allreduce(mesh: Mesh, inter_axis: str, intra_axis: str, x: Any, *,
                   n_inter: Optional[int] = None,
                   n_intra: Optional[int] = None, root: int = 0,
                   op: str = "sum", backend: str = "jnp") -> Any:
    """One-call hierarchical all-reduction."""
    return get_hier_comm(mesh, inter_axis, intra_axis,
                         backend=backend).allreduce(
        x, n_inter=n_inter, n_intra=n_intra, root=root, op=op)


def hier_allgather(mesh: Mesh, inter_axis: str, intra_axis: str, x: Any, *,
                   n_inter: Optional[int] = None,
                   n_intra: Optional[int] = None,
                   backend: str = "jnp") -> Any:
    """One-call hierarchical allgather (replicated rank-major result)."""
    return get_hier_comm(mesh, inter_axis, intra_axis,
                         backend=backend).allgather(
        x, n_inter=n_inter, n_intra=n_intra)


# ----------------------------------------------------- host data plans
#
# Single-process executions of the two-level data plane, composing the
# cached per-level host plans of repro.core.comm: phase A runs the
# level's kernels with the level's ranks batched on the kernel rows,
# the host-side re-blocking seam matches the device lowering's
# flatten/split, and phase B consumes phase A's output.  The simulator
# asserts these bit-exact against its message-passing reference -- the
# hierarchical certification path for both backends on CPU CI, at the
# full 36x32 scale no local device mesh could reach.


def _split_np(flat: np.ndarray, n: int) -> np.ndarray:
    """Host-side mirror of the device re-blocking: [m] -> [n, ceil(m/n)]."""
    flat = np.asarray(flat).reshape(-1)
    bs = -(-flat.shape[0] // n)
    out = np.zeros((n, bs), flat.dtype)
    out.reshape(-1)[: flat.shape[0]] = flat
    return out


def _reduce_sweep(values, nodes, cores, n_inter, n_intra, intra_red,
                  inter_red, root_node, root_core):
    """Host reduction sweep: [nodes, cores, m] contributions -> the flat
    [m] op-reduction at the root, via per-node intra reductions to the
    leaders then one inter reduction (a one-rank level passes through).
    Shared by the reduce and allreduce host plans."""
    vals = np.asarray(values).reshape(nodes, cores, -1)
    m = vals.shape[-1]
    if intra_red is not None:
        parts = []
        for j in range(nodes):
            blocked = np.stack([_split_np(vals[j, c], n_intra)
                                for c in range(cores)])
            parts.append(intra_red.run(blocked)[root_core].reshape(-1)[:m])
        partials = np.stack(parts)                    # [nodes, m]
    else:
        partials = vals[:, 0]
    if inter_red is not None:
        blocked = np.stack([_split_np(partials[j], n_inter)
                            for j in range(nodes)])
        return inter_red.run(blocked)[root_node].reshape(-1)[:m]
    return partials[0]


def _bcast_sweep(values, nodes, cores, n_inter, n_intra, inter_bc, intra_bc):
    """Host broadcast sweep: flat [m] payload at the root -> the final
    [nodes, cores, m] state of every rank, via the inter-node leader
    broadcast then the (node-identical) intra fan-out.  Per-level
    agreement of the leader copies is asserted.  Shared by the
    broadcast and allreduce host plans."""
    vals = np.asarray(values).reshape(-1)
    m = vals.shape[0]
    leader = vals
    if inter_bc is not None:
        got = inter_bc.run(_split_np(vals, n_inter))
        # every node leader ends with the root's payload
        leader = got[0].reshape(-1)[:m]
        for j in range(nodes):
            assert np.array_equal(got[j].reshape(-1)[:m], leader), (
                f"hier broadcast sweep: node leader {j} diverged")
    if intra_bc is not None:
        got = intra_bc.run(_split_np(leader, n_intra))
        percore = np.stack([got[c].reshape(-1)[:m] for c in range(cores)])
    else:
        percore = leader[None]
    return np.broadcast_to(percore[None], (nodes, cores, m))


@dataclass(frozen=True, eq=False)
class HierHostPlan:
    """Precomputed hierarchical host-side data-plane execution.

    Composes the cached flat :class:`~repro.core.comm.HostDataPlan`\\ s
    of each level; ``run(values)`` executes only the per-level rounds
    plus the re-blocking seam.
    """

    kind: str
    nodes: int
    cores: int
    n_inter: int
    n_intra: int
    root: int
    op: Optional[str]
    backend: str
    inter: Any = field(repr=False)   # flat HostDataPlan or None (level of 1)
    intra: Any = field(repr=False)

    @property
    def root_node(self) -> int:
        return self.root // self.cores

    @property
    def root_core(self) -> int:
        return self.root % self.cores

    @property
    def statics(self) -> Tuple[PhaseStatic, ...]:
        """Composed per-phase audit records in run order, delegated to
        the per-level flat host plans (a one-rank level contributes
        nothing)."""
        inter = self.inter.statics if self.inter is not None else ()
        intra = self.intra.statics if self.intra is not None else ()
        return inter + intra if self.kind == "broadcast" else intra + inter

    def run(self, values: np.ndarray) -> np.ndarray:
        if self.kind == "broadcast":
            return self._run_broadcast(values)
        if self.kind == "reduce":
            return self._run_reduce(values)
        # allreduce is always built as _AllreduceHostPlan (its levels
        # hold (reduce, broadcast) plan pairs this base class cannot run)
        assert self.kind == "allgather", self.kind
        return self._run_allgather(values)

    def _run_broadcast(self, values: np.ndarray) -> np.ndarray:
        """``values``: flat [m] payload at flat rank ``root`` -> final
        [nodes, cores, m] state of every rank."""
        return _bcast_sweep(values, self.nodes, self.cores, self.n_inter,
                            self.n_intra, self.inter, self.intra)

    def _run_reduce(self, values: np.ndarray) -> np.ndarray:
        """``values``: [nodes, cores, m] contributions -> flat [m]
        op-reduction (the state of flat rank ``root``)."""
        return _reduce_sweep(values, self.nodes, self.cores, self.n_inter,
                             self.n_intra, self.intra, self.inter,
                             self.root_node, self.root_core)

    def _run_allgather(self, values: np.ndarray) -> np.ndarray:
        """``values``: [nodes, cores, e] contributions -> flat
        [nodes*cores, e] rank-major gathered result (identical on every
        rank; per-level agreement asserted)."""
        vals = np.asarray(values).reshape(self.nodes, self.cores, -1)
        e = vals.shape[-1]
        if self.intra is not None:
            blocks = []
            for j in range(self.nodes):
                blocked = np.stack([_split_np(vals[j, c], self.n_intra)
                                    for c in range(self.cores)])
                got = self.intra.run(blocked)         # [C_rank, C_root, n, bs]
                node_block = got[0].reshape(self.cores, -1)[:, :e]
                for c in range(1, self.cores):
                    assert np.array_equal(
                        got[c].reshape(self.cores, -1)[:, :e], node_block), (
                        f"hier allgather: node {j} rank {c} diverged")
                blocks.append(node_block.reshape(-1))  # [cores * e]
            node_blocks = np.stack(blocks)            # [nodes, cores*e]
        else:
            node_blocks = vals[:, 0]
        if self.inter is not None:
            blocked = np.stack([_split_np(node_blocks[j], self.n_inter)
                                for j in range(self.nodes)])
            got = self.inter.run(blocked)             # [N_rank, N_root, n, bs]
            sz = node_blocks.shape[-1]
            out = got[0].reshape(self.nodes, -1)[:, :sz]
            for r in range(1, self.nodes):
                assert np.array_equal(
                    got[r].reshape(self.nodes, -1)[:, :sz], out), (
                    f"hier allgather: inter rank {r} diverged")
        else:
            out = node_blocks
        return out.reshape(self.nodes * self.cores, e)


def hier_host_plan(kind: str, nodes: int, cores: int, n_inter: int,
                   n_intra: int, *, root: int = 0, op: str = "sum",
                   backend: str = "jnp",
                   interpret: Optional[bool] = None) -> HierHostPlan:
    """The cached :class:`HierHostPlan` for a two-level certification
    execution.  ``kind``: broadcast / reduce / allreduce / allgather.
    Equal arguments return the identical plan object."""
    kind = _CANONICAL_KIND.get(kind, kind)
    if kind not in ("broadcast", "reduce", "allreduce", "allgather"):
        raise ValueError(f"unknown hier host data-plane kind {kind!r}")
    nodes, cores = int(nodes), int(cores)
    rooted = kind in ("broadcast", "reduce", "allreduce")
    root_key = int(root) if rooted else 0
    if not 0 <= root_key < max(1, nodes * cores):
        raise ValueError(f"root must be in [0, nodes*cores), got {root} for "
                         f"{nodes}x{cores}")
    op_key = op if kind in ("reduce", "allreduce") else None
    key = ("hierhostplan", kind, nodes, cores, int(n_inter), int(n_intra),
           root_key, op_key, backend, interpret)

    def build():
        rootN, rootC = divmod(root_key, cores)
        flat_kind = "allgather" if kind == "allgather" else (
            "reduce" if kind == "reduce" else "broadcast")

        def level(p, n, level_root):
            if p == 1:
                return None
            if flat_kind == "allgather":
                return host_plan("allgather", p, n, backend=backend,
                                 interpret=interpret)
            if flat_kind == "reduce":
                return host_plan("reduce", p, n, root=level_root, op=op_key,
                                 backend=backend, interpret=interpret)
            return host_plan("broadcast", p, n, root=level_root,
                             backend=backend, interpret=interpret)

        if kind == "allreduce":
            # the composed run needs both directions; cache the four flat
            # plans eagerly so run() is pure execution.
            inter = (host_plan("reduce", nodes, n_inter, root=rootN,
                               op=op_key, backend=backend,
                               interpret=interpret),
                     host_plan("broadcast", nodes, n_inter, root=rootN,
                               backend=backend, interpret=interpret)
                     ) if nodes > 1 else None
            intra = (host_plan("reduce", cores, n_intra, root=rootC,
                               op=op_key, backend=backend,
                               interpret=interpret),
                     host_plan("broadcast", cores, n_intra, root=rootC,
                               backend=backend, interpret=interpret)
                     ) if cores > 1 else None
            return _AllreduceHostPlan(
                kind=kind, nodes=nodes, cores=cores, n_inter=int(n_inter),
                n_intra=int(n_intra), root=root_key, op=op_key,
                backend=backend, inter=inter, intra=intra)
        return HierHostPlan(
            kind=kind, nodes=nodes, cores=cores, n_inter=int(n_inter),
            n_intra=int(n_intra), root=root_key, op=op_key, backend=backend,
            inter=level(nodes, n_inter, rootN),
            intra=level(cores, n_intra, rootC))

    return cached_plan(key, build)


@dataclass(frozen=True, eq=False)
class _AllreduceHostPlan(HierHostPlan):
    """Hier allreduce host plan: per level, ``inter``/``intra`` hold a
    (reduce_plan, broadcast_plan) pair instead of one flat plan; the
    run is the reduction sweep followed by the broadcast sweep."""

    @property
    def statics(self) -> Tuple[PhaseStatic, ...]:
        red_n, bc_n = self.inter if self.inter is not None else (None, None)
        red_c, bc_c = self.intra if self.intra is not None else (None, None)
        out: Tuple[PhaseStatic, ...] = ()
        for plan in (red_c, red_n, bc_n, bc_c):  # the composed run order
            if plan is not None:
                out = out + plan.statics
        return out

    def run(self, values: np.ndarray) -> np.ndarray:
        red_n, bc_n = self.inter if self.inter is not None else (None, None)
        red_c, bc_c = self.intra if self.intra is not None else (None, None)
        total = _reduce_sweep(values, self.nodes, self.cores, self.n_inter,
                              self.n_intra, red_c, red_n,
                              self.root_node, self.root_core)
        return _bcast_sweep(total, self.nodes, self.cores, self.n_inter,
                            self.n_intra, bc_n, bc_c)
