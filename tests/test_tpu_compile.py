"""Ahead-of-time compiles for a described TPU v5e (no chip needed).

The TPU compiler is installed with jax; it compiles for a topology that
is described rather than attached.  These tests guard what interpret
mode cannot see: every round-step kernel must lower through Mosaic at
deployment block sizes (tile-aligned blocks, scoped-VMEM budget), and
the jnp-backend broadcast plan must lower to the schedule's round count
of ``collective-permute``s on a 4-chip mesh, the jnp round step's
slot reads and writes in the allreduce cell and the quantized ones in
the gradient sync must start on tile boundaries, no large array of a
rank stack may lay its rank axis minor, every benchmark cell's program
must compile, and the gradient sync's permutes must match its static
counters.

The topology is described inside a module fixture, never at import: one
process at a time may load the TPU library, and the suite runs on
several workers.
"""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import block_pack as bp

os.environ.setdefault("TPU_LOG_DIR", "disabled")

R = 4                          # one row per rank of a p = 4 plan
NSLOTS = 10                    # n = 8 blocks + garbage + identity slot
SIZES = (4 << 10, 1 << 20, 16 << 20)   # bytes per block


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # Compiles here cannot be read back from the persistent cache
    # without a chip; keep them out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _arg(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_args(name, nbytes, dtype, sh):
    """ShapeDtypeStructs for kernel ``name`` at ``nbytes`` per block, in
    the tiled slot layout the plans hold."""
    idx = _arg((R,), jnp.int32, sh)
    if name == "block_qacc_shuffle":
        shape = bp.slot_shape(nbytes // 4, jnp.float32, qblock=256)
        buf = _arg((R, NSLOTS) + shape, jnp.float32, sh)
        return (buf, buf, _arg((R,) + shape, jnp.int8, sh),
                _arg((R, shape[0]), jnp.float32, sh), idx, idx)
    shape = bp.slot_shape(nbytes // np.dtype(dtype).itemsize, dtype)
    buf = _arg((R, NSLOTS) + shape, dtype, sh)
    msg = _arg((R,) + shape, dtype, sh)
    return {
        "block_pack": (buf, idx),
        "block_unpack": (buf, msg, idx),
        "block_shuffle": (buf, msg, idx, idx),
        "block_shuffle_staged": (buf, msg, msg, idx, idx),
        "block_acc_shuffle": (buf, msg, idx, idx),
        "block_acc_shuffle_staged": (buf, msg, msg, idx, idx),
    }[name]


@pytest.mark.parametrize("nbytes", SIZES)
@pytest.mark.parametrize("name", bp.KERNEL_NAMES)
def test_kernel_compiles_for_v5e(one_chip, name, nbytes):
    fn = getattr(bp, name)
    args = _kernel_args(name, nbytes, jnp.float32, one_chip)
    compiled = jax.jit(lambda *a: fn(*a, interpret=False)).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name,dtype", [
    ("block_shuffle", jnp.bfloat16), ("block_shuffle", jnp.int32),
    ("block_shuffle", jnp.int8), ("block_acc_shuffle", jnp.bfloat16),
    ("block_acc_shuffle", jnp.int32)])
def test_fused_kernels_compile_for_payload_dtypes(one_chip, name, dtype):
    fn = getattr(bp, name)
    args = _kernel_args(name, 1 << 20, dtype, one_chip)
    compiled = jax.jit(lambda *a: fn(*a, interpret=False)).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "name", [k for k in bp.KERNEL_NAMES if k != "block_qacc_shuffle"])
def test_kernels_compile_under_x64(one_chip, name):
    """The exact kinds' host data plans certify in x64 mode (the
    quantized one runs in f32); the kernels' index maps must stay 32-bit
    there (Mosaic refuses 64-bit block indices)."""
    fn = getattr(bp, name)
    with jax.enable_x64(True):
        args = _kernel_args(name, 1 << 20, jnp.float32, one_chip)
        compiled = jax.jit(lambda *a: fn(*a, interpret=False)).lower(
            *args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shift", [-3, -2, -1, 1, 2, 3])
def test_host_plan_exchange_compiles_for_v5e(one_chip, shift):
    """The host plans' rank rotation on a [p, bs] message of one 25 MiB
    bucket split in 8 (``jnp.roll`` by 2 rows aborted this compiler)."""
    from repro.core.comm import _rotate

    msg = _arg((4, 819200), jnp.float32, one_chip)
    with jax.enable_x64(True):
        jax.jit(lambda m: _rotate(m, shift)).lower(msg).compile()


def test_broadcast_plan_lowers_to_schedule_permutes(topo):
    from repro.core.comm import CirculantComm

    p, n = 4, 8
    mesh = Mesh(np.array(topo.devices[:p]), ("data",))
    comm = CirculantComm(mesh, "data")
    x = _arg((p, 1 << 20), jnp.float32, NamedSharding(mesh, P("data")))
    plan = comm.plan("broadcast", x, n_blocks=n)
    assert plan.rounds == n - 1 + 2
    text = jax.jit(plan).lower(x).compile().as_text()
    starts = text.count("collective-permute-start(")
    assert (starts or text.count("collective-permute(")) == plan.rounds


# What stays unscoped in each benchmark cell's entry computation after
# inheritance (bench/scopes.py), by opcode: in the allreduce the
# partition index and copies of the input and of scalars into other
# memory (the split of the tile-stacked slots is one pad, so no zero
# slot buffer is filled outside a scope); in the rank stacks nothing;
# in the one-rank-per-chip gradient sync the same as the allreduce,
# with two copies more into other memory.
UNSCOPED = {
    "ddp_allreduce.25m": {
        "partition-id": 1, "and": 1, "convert": 1, "copy-start": 4,
        "copy-done": 4},
    "int8_gradsync.4m.rankstack": {},
    "int8_gradsync.25m": {
        "partition-id": 1, "and": 1, "convert": 1, "copy-start": 6,
        "copy-done": 6},
    "int8_gradsync.25m.rankstack": {},
}
NOT_WORK = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
            "while", "conditional", "call", "collective-permute-start",
            "collective-permute-done"}


_COMPILED = {}


def _bench_compile(name, topo):
    """The HLO text of a benchmark cell's timed program, compiled once
    per module run."""
    import sys
    from pathlib import Path

    if name not in _COMPILED:
        root = str(Path(__file__).resolve().parents[1])
        if root not in sys.path:
            sys.path.insert(0, root)
        from bench.aot import compile_cell

        _COMPILED[name] = compile_cell(name, topo)[0].as_text()
    return _COMPILED[name]


@pytest.mark.parametrize("name", sorted(UNSCOPED))
def test_cell_programs_name_their_work(topo, name):
    """Every instruction of the cell's entry computation that does work
    carries a program scope or inherits one, but a stated remainder."""
    from collections import Counter

    from bench.scopes import instruction_scopes, parse

    text = _bench_compile(name, topo)
    comps, entry = parse(text)
    scopes = instruction_scopes(text)
    work = [i for i in comps[entry] if i.opcode not in NOT_WORK]
    unscoped = Counter(i.opcode for i in work if scopes[i.name] is None)
    assert dict(unscoped) == UNSCOPED[name]
    assert any(s.startswith("roundstep.") for s in scopes.values() if s)


def test_plan_permutes_and_wire_bytes_match_compiled_hlo(topo):
    """The plans' static counters equal the collective-permutes of their
    TPU executables and those permutes' bytes, as counted by
    repro.launch.hlo_analysis (whose pattern reads the TPU compiler's
    tuple-shaped asynchronous permutes)."""
    from repro.core.comm import CirculantComm
    from repro.launch.hlo_analysis import collective_stats

    p = 4
    mesh = Mesh(np.array(topo.devices[:p]), ("x",))
    sh = NamedSharding(mesh, P("x"))
    comm = CirculantComm(mesh, "x")
    ddp = _arg((p, 26214400 // 4), jnp.float32, sh)
    small = _arg((p, 1 << 20), jnp.float32, sh)
    for plan, x, permutes in (
            (comm.plan("allreduce", ddp), ddp, 48),
            (comm.plan("quantized_allreduce", small, n_blocks=8), small, 36)):
        assert plan.permutes == permutes
        stats = collective_stats(jax.jit(plan).lower(x).compile().as_text())
        assert stats.ops_by_kind == {"collective-permute": permutes}
        assert stats.bytes_by_kind["collective-permute"] == plan.wire_bytes


def _permute_scopes(text):
    """The program scope of each collective-permute-start."""
    from collections import Counter

    from bench.scopes import instruction_scopes

    return Counter(s for n, s in instruction_scopes(text).items()
                   if n.startswith("collective-permute-start"))


@pytest.mark.parametrize("name,scales", [("ddp_allreduce.25m", 0),
                                         ("int8_gradsync.25m", 24)])
def test_only_the_quantized_scales_permutes_are_scales_scoped(topo, name,
                                                              scales):
    """The gradient sync sends its per-block scales in 24 of its 48
    permutes, under ``circulant.scales``, and its int8 blocks in the
    other 24 under the phases' scopes; the f32 allreduce's 48 stay in
    its phases' scopes."""
    from repro.core import tracing

    scopes = _permute_scopes(_bench_compile(name, topo))
    assert sum(scopes.values()) == 48
    assert scopes[tracing.SCALES] == scales
    assert set(scopes) - {tracing.SCALES} <= {
        tracing.REDUCE, tracing.QREDUCE, tracing.BCAST}


def test_grad_sync_counters_match_compiled_hlo(topo):
    """The trainer path's static counters (``grad_sync_counters``) equal
    the collective-permutes of the one-rank-per-chip gradient sync's
    TPU executable and their bytes, and the scales' bytes those of the
    permutes under ``circulant.scales``."""
    from repro.core import tracing
    from repro.launch.hlo_analysis import collective_stats
    from repro.optim.compression import grad_sync_counters, make_bucket_spec

    elems = 26214400 // 4
    text = _bench_compile("int8_gradsync.25m", topo)
    spec = make_bucket_spec(jax.ShapeDtypeStruct((elems,), jnp.float32),
                            26214400)
    got = grad_sync_counters(spec, 4)
    assert (got.n_blocks, got.rounds, got.permutes) == (11, 24, 48)
    # the reduce buffer of n + 2 quantized slots, each a tile stack
    assert got.tiled_qslots == 1
    assert "f32[1,13,2336,256]{" in text and "f32[1,13,595968]" not in text
    stats = collective_stats(text)
    assert stats.ops_by_kind == {"collective-permute": got.permutes}
    assert stats.bytes_by_kind["collective-permute"] == got.wire_bytes
    # the same module with only the scales' permutes left in it
    scales = collective_stats("\n".join(
        ln for ln in text.splitlines()
        if "collective-permute-start(" not in ln or tracing.SCALES in ln))
    assert scales.ops_by_kind == {"collective-permute": got.permutes // 2}
    assert scales.bytes_by_kind["collective-permute"] == got.scales_wire_bytes


_LINE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?\s([a-z][a-z0-9\-]*)\(")
_COMP_RE = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_CALLS_RE = re.compile(r"\bcalls=%?([\w.\-]+)")
_ALIGNED_RE = re.compile(r'"is_index_aligned":\[([a-z,]*)\]')


def _roundstep_slot_accesses(text):
    """``(instruction, scope, is_index_aligned)`` of every dynamic-slice
    and dynamic-update-slice under a ``roundstep.*`` scope: its own
    ``op_name``'s, else that of the fusion whose computation holds it
    (fused roots included), else the one it inherits."""
    from bench.scopes import instruction_scopes, scope_of

    scopes = instruction_scopes(text)
    parsed, fused_scope, comp = [], {}, None
    for line in text.splitlines():
        c = _COMP_RE.match(line)
        if c and "=" not in line.split("{", 1)[0].split("(", 1)[0]:
            comp = c.group(1)
            continue
        m = _LINE_RE.match(line)
        if not m:
            continue
        op = _OP_NAME_RE.search(line)
        own = scope_of(op.group(1)) if op else None
        calls = _CALLS_RE.search(line)
        if m.group(2) == "fusion" and calls:
            fused_scope[calls.group(1)] = scopes.get(m.group(1)) or own
        aligned = _ALIGNED_RE.search(line)
        parsed.append((comp, m.group(1), m.group(2), own,
                       aligned.group(1) if aligned else None))
    out = []
    for comp, name, opcode, own, aligned in parsed:
        if opcode not in ("dynamic-slice", "dynamic-update-slice"):
            continue
        scope = (own if own and own.startswith("roundstep.")
                 else scopes.get(name) or fused_scope.get(comp) or own)
        if scope and scope.startswith("roundstep."):
            out.append((name, scope, aligned))
    return out


def test_roundstep_slot_accesses_are_tile_aligned(topo):
    """In the allreduce cell every round-step slot read and write starts
    on a tile boundary in every dimension: the slot index lies outside
    the (8, 128) tile, so a round touches whole tiles of one slot and
    not one sublane of every tile of the buffer."""
    text = _bench_compile("ddp_allreduce.25m", topo)
    accesses = _roundstep_slot_accesses(text)
    assert {s for _, s, _ in accesses} >= {
        "roundstep.acc_shuffle", "roundstep.shuffle", "roundstep.unpack"}
    unaligned = [(n, s, a) for n, s, a in accesses
                 if a is None or "false" in a]
    assert not unaligned, unaligned[:5]


def test_quantized_slot_accesses_are_tile_aligned(topo):
    """In the one-rank-per-chip gradient sync every quantized round
    step's slot read and write starts on a tile boundary in every
    dimension: a quantized slot is a ``(rows, qblock)`` tile stack, so
    a reduce round touches whole tiles of one slot of the buffer and of
    its error twin."""
    text = _bench_compile("int8_gradsync.25m", topo)
    accesses = [a for a in _roundstep_slot_accesses(text)
                if a[1] == "roundstep.qacc_shuffle"]
    assert len(accesses) >= 24
    unaligned = [a for a in accesses if a[2] is None or "false" in a[2]]
    assert not unaligned, unaligned[:5]


_RANK_MINOR_RE = re.compile(
    r"=\s*([a-z]+)(\d*)\[4,([\d,]+)\]\{0[,}]")


@pytest.mark.parametrize("name", ["int8_gradsync.4m.rankstack",
                                  "int8_gradsync.25m.rankstack"])
def test_rank_stacks_keep_the_rank_axis_major(topo, name):
    """No array of more than 1 MB in a rank stack's executable lays its
    rank axis (dim 0, the 4 stacked ranks) minor: that layout pads 4
    ranks to 128 lanes, 32 times the array's bytes."""
    text = _bench_compile(name, topo)
    minor = []
    for line in text.splitlines():
        m = _RANK_MINOR_RE.search(line)
        if not m:
            continue
        bits = int(m.group(2) or 8)            # pred is a byte
        elems = 4 * int(np.prod([int(d) for d in m.group(3).split(",")]))
        if elems * bits // 8 > 1 << 20:
            minor.append(line.strip()[:120])
    assert not minor, minor[:5]


def test_plan_counts_tiled_leaves(topo):
    """The ddp bucket's slots take the tile stack, a 4 KiB payload's
    stay flat, and describe() says so; likewise a quantized plan's
    reduce slot."""
    from repro.core.comm import CirculantComm

    p = 4
    mesh = Mesh(np.array(topo.devices[:p]), ("x",))
    sh = NamedSharding(mesh, P("x"))
    comm = CirculantComm(mesh, "x")
    ddp = comm.plan("allreduce", _arg((p, 26214400 // 4), jnp.float32, sh))
    small = comm.plan("allreduce", _arg((p, 4096 // 4), jnp.float32, sh),
                      n_blocks=4)
    assert (ddp.n_blocks, ddp.tiled_leaves) == (23, 1)
    assert "tiled_leaves=1" in ddp.describe()
    assert small.tiled_leaves == 0
    assert ddp.tiled_qslots == 0 and "tiled_qslots" not in ddp.describe()
    # a quantized plan's reduce slot: 1 Mi elements in 8 blocks tile,
    # 300 elements in 2 stay flat
    qbig = comm.plan("quantized_allreduce",
                     _arg((p, 1 << 20), jnp.float32, sh), n_blocks=8)
    qsmall = comm.plan("quantized_allreduce",
                       _arg((p, 300), jnp.float32, sh), n_blocks=2)
    assert (qbig.tiled_qslots, qsmall.tiled_qslots) == (1, 0)
    assert "tiled_qslots=1" in qbig.describe()
