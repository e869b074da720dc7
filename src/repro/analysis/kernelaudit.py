"""Pallas data-plane race detector for the schedule-driven kernels.

The kernels in :mod:`repro.kernels.block_pack` publish a machine-checkable
:class:`~repro.kernels.block_pack.KernelAudit` record: the grid, every
operand's BlockSpec index map (the *same* function objects the
``pallas_call`` was built with), which logical HBM storage each operand
addresses, the ``input_output_aliases`` dict, and a liveness predicate
saying at which grid points an input block's value is actually consumed.

Pallas executes the grid sequentially in lexicographic order but
*pipelines* the block DMAs: an input block may be fetched before a
logically earlier grid point's output write has landed.  Interpret mode
has no such pipeline, so any value that depends on reading back a block
a strictly earlier grid point wrote can differ between ``interpret=True``
CI and the compiled TPU run -- the exact hazard the fused kernels were
rewritten to avoid (read-only operand + staging scratch).  This pass
proves the absence of that hazard *statically*, by replaying the index
maps over the whole grid with the real schedule tables:

  * **write-write overlap**: two grid points writing the same block of
    one storage, outside the declared sequential drain dimension
    (``drain_dims`` -- the accumulate-then-drain sub-round rewriting one
    row's slot is by-design sequential);
  * **read-after-write alias**: a *live* input read of a block that a
    strictly earlier grid point wrote (dead fetches -- the alias
    operand's discarded block, the drain sub-round's staged-through
    reads -- cannot race);
  * **alias map consistency**: every ``input_output_aliases`` pair must
    address identical blocks at every grid point, else the alias
    rewrites a block the input never fetched;
  * **trace consistency**: the jaxpr actually traced from each kernel
    carries the registry's grid and alias pairs (the registry cannot
    silently drift from the shipped ``pallas_call``);
  * **dtype discipline**: traced output dtypes equal the declared
    ``out_dtypes`` contract -- accumulate in the buffer dtype, int8 wire
    + f32 scales in the quantized path, no silent widening/narrowing.

Schedule tables come from the same process-wide cached slot plans the
plans execute, so a clean audit speaks about the shipped data plane, not
a synthetic one.  This module imports jax (tracing only -- nothing is
executed); :mod:`repro.analysis` loads it lazily to keep the host-plane
entry points jax-free.
"""

from __future__ import annotations

import itertools
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from .report import Finding, Report

__all__ = [
    "replay_kernel",
    "audit_kernel_trace",
    "audit_kernel",
    "audit_kernels",
    "schedule_scalars",
]

_DTYPES = ("float32", "bfloat16", "int32")  # acc paths under audit


def _find(out: List[Finding], check: str, location: str, message: str) -> None:
    out.append(Finding(pass_name="kernel", check=check, location=location,
                       message=message))


def _eval_map(index_map, g: Tuple[int, ...],
              scalars: Sequence[np.ndarray]) -> Tuple[int, ...]:
    """Evaluate a BlockSpec index map at concrete grid point g with the
    prefetched scalar tables (numpy stands in for the SMEM refs)."""
    return tuple(int(c) for c in index_map(*g, *scalars))


def replay_kernel(spec, scalars: Sequence[np.ndarray],
                  out: Optional[List[Finding]] = None,
                  location: str = "") -> List[Finding]:
    """Replay one kernel's index maps over its grid and prove the three
    structural properties (WW overlap, live RAW, alias-map agreement).

    ``spec`` is a :class:`~repro.kernels.block_pack.KernelAudit`;
    ``scalars`` the concrete int32 prefetch vectors (one per scalar
    name, typically a round row of the cached slot tables).
    """
    out = [] if out is None else out
    loc = location or spec.name
    if len(scalars) != len(spec.scalar_names):
        _find(out, "scalar-arity", loc,
              f"{len(scalars)} scalar vectors for prefetch names "
              f"{spec.scalar_names}")
        return out
    scalars = [np.asarray(s) for s in scalars]

    grid_points = list(itertools.product(*(range(d) for d in spec.grid)))
    order = {g: i for i, g in enumerate(grid_points)}

    # writes[(storage, block)] -> list of grid points that wrote it
    writes: Dict[Tuple[str, Tuple[int, ...]], List[Tuple[int, ...]]] = {}
    for op in spec.outputs:
        for g in grid_points:
            blk = _eval_map(op.index_map, g, scalars)
            key = (op.storage, blk)
            prev = writes.setdefault(key, [])
            for earlier in prev:
                diff = tuple(d for d in range(len(g)) if earlier[d] != g[d])
                if not all(d in spec.drain_dims for d in diff):
                    _find(out, "ww-overlap", f"{loc}@{g}",
                          f"output {op.name!r} rewrites {op.storage} block "
                          f"{blk} already written at grid point {earlier} "
                          f"(differing dims {diff} not all in drain_dims "
                          f"{spec.drain_dims})")
            prev.append(g)

    # live reads vs strictly-earlier writes (the pipeline hazard)
    for op in spec.inputs:
        for g in grid_points:
            if op.live is not None and not op.live(g):
                continue
            blk = _eval_map(op.index_map, g, scalars)
            for w in writes.get((op.storage, blk), ()):
                if order[w] < order[g]:
                    _find(out, "raw-alias", f"{loc}@{g}",
                          f"live input {op.name!r} reads {op.storage} block "
                          f"{blk} written at earlier grid point {w}; "
                          f"compiled prefetch may observe either value "
                          f"(interpret/compiled divergence)")

    # alias pairs must address the same block everywhere
    for in_idx, out_idx in spec.aliases:
        pos = in_idx - spec.num_scalar_prefetch
        if not (0 <= pos < len(spec.inputs)) or out_idx >= len(spec.outputs):
            _find(out, "alias-range", loc,
                  f"alias pair ({in_idx}, {out_idx}) outside the operand "
                  f"layout ({len(spec.inputs)} inputs + "
                  f"{spec.num_scalar_prefetch} prefetch, "
                  f"{len(spec.outputs)} outputs)")
            continue
        i_op, o_op = spec.inputs[pos], spec.outputs[out_idx]
        if i_op.storage != o_op.storage:
            _find(out, "alias-storage", loc,
                  f"aliased operands {i_op.name!r}/{o_op.name!r} declare "
                  f"different storages ({i_op.storage!r} vs "
                  f"{o_op.storage!r})")
        for g in grid_points:
            bi = _eval_map(i_op.index_map, g, scalars)
            bo = _eval_map(o_op.index_map, g, scalars)
            if bi != bo:
                _find(out, "alias-map", f"{loc}@{g}",
                      f"alias pair {i_op.name!r}->{o_op.name!r} fetches "
                      f"block {bi} but writes block {bo}; the in-place "
                      f"update would land in a block never fetched")
                break
    return out


# ----------------------------------------------------- trace consistency


def _traced_pallas_params(name: str, R: int, nslots: int, bs: int, nb: int,
                          dtype) -> Tuple[Optional[dict], Tuple]:
    """(pallas_call eqn params, traced out dtypes) for kernel ``name``
    called as the plans call it: compiled (``interpret=False``) on
    buffers already in the tiled slot layout.

    Tracing only -- jax.make_jaxpr over shape structs never executes
    the kernel or allocates its operands, so this is cheap and
    device-free at any block size.
    """
    import functools

    import jax
    import jax.numpy as jnp

    from repro.kernels import block_pack as bp

    S = jax.ShapeDtypeStruct
    idx = S((R,), jnp.int32)
    if name == "block_qacc_shuffle":
        slot = bp.slot_shape(bs, jnp.float32, qblock=bs // nb)
        buf = S((R, nslots) + slot, jnp.float32)
        args = (buf, buf, S((R,) + slot, jnp.int8),
                S((R, slot[0]), jnp.float32), idx, idx)
    else:
        slot = bp.slot_shape(bs, dtype)
        buf = S((R, nslots) + slot, dtype)
        msg = S((R,) + slot, dtype)
        args = {
            "block_pack": (buf, idx),
            "block_unpack": (buf, msg, idx),
            "block_shuffle": (buf, msg, idx, idx),
            "block_shuffle_staged": (buf, msg, msg, idx, idx),
            "block_acc_shuffle": (buf, msg, idx, idx),
            "block_acc_shuffle_staged": (buf, msg, msg, idx, idx),
        }[name]
    fn = functools.partial(getattr(bp, name), interpret=False)
    jaxpr = jax.make_jaxpr(fn)(*args)
    outs = tuple(v.aval.dtype for v in jaxpr.jaxpr.outvars)
    for eqn in jaxpr.eqns:
        if "pallas" in eqn.primitive.name:
            return eqn.params, outs
    return None, outs


def audit_kernel_trace(name: str, *, R: int = 3, nslots: int = 4,
                       bs: int = 8, nb: int = 2,
                       out: Optional[List[Finding]] = None,
                       spec=None) -> List[Finding]:
    """Trace kernel ``name`` to a jaxpr and check the registry cannot
    have drifted from the shipped pallas_call: same grid, same alias
    pairs, declared output dtypes.  ``spec`` overrides the registry
    record (the negative tests inject corrupted ones)."""
    import numpy as _np

    from repro.kernels import block_pack as bp

    out = [] if out is None else out
    registry_spec = spec
    dtypes = ("float32",) if name == "block_qacc_shuffle" else _DTYPES
    for dt in dtypes:
        spec = registry_spec if registry_spec is not None else \
            bp.kernel_audit_spec(name, R=R, nslots=nslots, bs=bs, nb=nb,
                                 dtype=_np.dtype(dt))
        loc = f"{name}[{dt}]"
        params, traced_out = _traced_pallas_params(
            name, R, nslots, bs, nb, _np.dtype(dt))
        if params is None:
            _find(out, "trace-missing", loc,
                  "no pallas_call primitive in the traced jaxpr")
            continue
        gm = params.get("grid_mapping")
        grid = getattr(gm, "grid", None)
        if grid is not None and tuple(grid) != spec.grid:
            _find(out, "trace-grid", loc,
                  f"traced grid {tuple(grid)} != registry grid {spec.grid}")
        nsp = getattr(gm, "num_index_operands", None)
        if nsp is not None and nsp != spec.num_scalar_prefetch:
            _find(out, "trace-prefetch", loc,
                  f"traced num_index_operands {nsp} != registry "
                  f"{spec.num_scalar_prefetch}")
        ioa = params.get("input_output_aliases")
        if ioa is not None and tuple(sorted(tuple(map(int, p)) for p in ioa)) \
                != tuple(sorted(spec.aliases)):
            _find(out, "trace-alias", loc,
                  f"traced input_output_aliases {tuple(ioa)} != registry "
                  f"{spec.aliases}")
        want = tuple(_np.dtype(d) for d in spec.out_dtypes(_np.dtype(dt)))
        got = tuple(_np.dtype(d) for d in traced_out)
        if got != want:
            _find(out, "dtype-widening", loc,
                  f"traced output dtypes {tuple(str(d) for d in got)} != "
                  f"declared {tuple(str(d) for d in want)}")
    return out


# ------------------------------------------------------------ full sweep


def schedule_scalars(name: str, p: int, n: int,
                     root: int = 0) -> Tuple[int, List[Tuple[np.ndarray, ...]]]:
    """(nslots, per-round scalar vectors) for kernel ``name`` driven by
    the real cached slot plans of a p-rank n-block schedule.

    The replay then audits exactly the index-map/table combinations the
    round-step backends execute.
    """
    from repro.core.engine import get_bundle
    from repro.core.roundstep import broadcast_slot_plan, reduce_slot_plan

    bundle = get_bundle(p, root)
    if name in ("block_pack", "block_unpack", "block_shuffle",
                "block_shuffle_staged"):
        recv, send, _ks = broadcast_slot_plan(bundle, n)
        nslots = n + 1
        if name == "block_pack":
            rows = [(send[t],) for t in range(len(send))]
        elif name == "block_unpack":
            rows = [(recv[t],) for t in range(len(recv))]
        else:  # (staged) shuffle: unpack round t, pack round t+1
            rows = [(recv[t], send[t + 1]) for t in range(len(recv) - 1)]
        return nslots, rows
    fwd, acc, _ks = reduce_slot_plan(bundle, n)
    nslots = n + 2
    # accumulate round t, capture/drain round t+1
    return nslots, [(acc[t], fwd[t + 1]) for t in range(len(fwd) - 1)]


def audit_kernel(name: str, p: int, n: int, root: int = 0,
                 bs: Optional[int] = None) -> Report:
    """Structural replay of one kernel over every round of a real
    p-rank n-block schedule, plus the trace/dtype checks.

    The default block of ``bs`` elements spans several row tiles of
    every audited dtype, so the replay covers the tile grid axis too.
    """
    from repro.kernels import block_pack as bp
    from repro.kernels.quant_ops import QBLOCK

    findings: List[Finding] = []
    nslots, rows = schedule_scalars(name, p, n, root)
    if bs is None:
        bs = 3 * bp.MAX_BLOCK_BYTES // 4
    nb = max(1, bs // QBLOCK)
    spec = bp.kernel_audit_spec(name, R=p, nslots=nslots, bs=bs, nb=nb)
    checked = 0
    for t, scalars in enumerate(rows):
        replay_kernel(spec, scalars, findings,
                      location=f"{name} p={p} n={n} round {t}")
        checked += 1
    audit_kernel_trace(name, R=p, nslots=nslots, bs=bs, nb=nb, out=findings)
    return Report(findings=tuple(findings), checked=checked + 1)


def audit_kernels(ps: Iterable[int] = (2, 3, 5, 8), ns: Iterable[int] = (1, 4),
                  names: Optional[Iterable[str]] = None) -> Report:
    """Audit every registered kernel against a grid of real schedules."""
    from repro.kernels import block_pack as bp

    report = Report()
    for name in (bp.KERNEL_NAMES if names is None else names):
        for p in ps:
            for n in ns:
                report = report + audit_kernel(name, int(p), int(n))
    return report
