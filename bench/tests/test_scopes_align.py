"""The program scopes of compiled instructions (``bench/scopes.py``), the
device clocks put on the host's (``bench/align.py``) and the readers
built on them, on hand-written HLO and synthetic traces; a guard that
the readers that came before read the recorded traces as they did; and
the readers on traces of the scoped program recorded on the chip."""

from pathlib import Path

import pytest

from bench import align, harness
from bench import scopes as sc
from bench import trace as tr
from bench.reading import Reading

DATA = Path(__file__).parent / "data"
PEAKS = {"hbm_bytes_per_s": 819e9, "ici_bytes_per_s": 200e9}
PARTS = ("roundstep_ms", "layout_ms", "unscoped_ms", "roundloop_ms")


def ev(name, a, b):
    return tr.Event(name, float(a), float(b))


def read(metric, r):
    return harness.load_module(
        harness.ROOT / "bench" / "metrics" / f"{metric}.py",
        f"test_scopes_metric_{metric}").read(r)


def reading(trace, hlo="", dispatch=(), hbm=0.0, ici=0.0):
    return Reading(trace=trace, hlo=hlo, dispatch_s=list(dispatch),
                   least_hbm_bytes=hbm, least_ici_bytes=ici, peaks=PEAKS)


# --------------------------------------------------------------- scopes


def test_scope_of_takes_the_innermost_round_step_else_program_name():
    assert sc.scope_of("jit(f)/shard_map/circulant.reduce/roundstep."
                       "acc_shuffle/jit(block_acc_shuffle_ref)/scatter") == (
        "roundstep.acc_shuffle")
    assert sc.scope_of("jit(f)/circulant.bcast/circulant.join/slice") == (
        "circulant.join")
    assert sc.scope_of("jit(body)/vmap(circulant.qreduce)/jit(_take)/"
                       "gather") == "circulant.qreduce"
    assert sc.scope_of("jit(f)/vmap(gradsync.bucket)/concatenate") == (
        "gradsync.bucket")
    assert sc.scope_of("jit(f)/shard_map/axis_index") is None
    assert sc.scope_of("jit(my_circulant.x)/add") is None


HLO = """HloModule m, is_scheduled=true

%body.1 (p.1: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p.1 = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%p.1), index=1
  %copy.9 = f32[8]{0} copy(%gte.1)
  ROOT %t.1 = (s32[], f32[8]{0}) tuple(%gte.0, %copy.9)
}

%fused_computation.2 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %neg.3 = f32[8]{0} negate(%param_0), metadata={op_name="jit(f)/circulant.split/neg"}
}

ENTRY %main.5 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %copy-start = (f32[8]{0:S(1)}, f32[8]{0}, u32[]) copy-start(%x)
  %copy-done = f32[8]{0:S(1)} copy-done(%copy-start)
  %fusion.2 = f32[8]{0} fusion(%copy-done), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(f)/shard_map/circulant.reduce/circulant.split/neg"}
  %scatter.4 = f32[8]{0} scatter(f32[8]{0} %fusion.2, s32[1]{0} %i, f32[1]{0} %u), metadata={op_name="jit(f)/circulant.reduce/roundstep.acc_shuffle/jit(g)/scatter"}
  %copy.5 = f32[8]{0} copy(%scatter.4)
  %dynamic-update-slice.6 = f32[8]{0} dynamic-update-slice(%broadcast.7, %copy.5, %c)
  %while.8 = (s32[], f32[8]{0}) while(%tuple.9), condition=%cond.1, body=%body.1, metadata={op_name="jit(f)/circulant.bcast/roundstep.shuffle/while"}
  %fusion.10 = f32[8]{0} fusion(%copy.5), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(f)/circulant.bcast/circulant.join/slice"}
  ROOT %add.11 = f32[8]{0} add(%x, %x), metadata={op_name="jit(f)/circulant.bcast/add"}
}
"""


def test_parse_reads_names_opcodes_operands_and_callees():
    comps, entry = sc.parse(HLO)
    assert entry == "main.5"
    assert set(comps) == {"body.1", "fused_computation.2", "main.5"}
    by_name = {i.name: i for i in comps["main.5"]}
    assert by_name["scatter.4"].opcode == "scatter"
    assert by_name["scatter.4"].operands == ["fusion.2", "i", "u"]
    assert by_name["while.8"].callees == ["cond.1", "body.1"]
    assert by_name["copy-start"].scope is None


def test_instruction_scopes_inherit_through_operands_and_control_flow():
    s = sc.instruction_scopes(HLO)
    assert s["fusion.2"] == "circulant.split"
    assert s["scatter.4"] == "roundstep.acc_shuffle"
    # no op_name: the first operand with a scope, transitively
    assert s["copy.5"] == "roundstep.acc_shuffle"
    assert s["dynamic-update-slice.6"] == "roundstep.acc_shuffle"
    # the body of a while inherits the while's scope
    assert s["copy.9"] == "roundstep.shuffle"
    assert s["fusion.10"] == "circulant.join"
    assert s["add.11"] == "circulant.bcast"
    # the input's copies have nothing to inherit from
    assert s["copy-start"] is None and s["copy-done"] is None
    # instructions inside fusions have no events of their own
    assert "neg.3" not in s
    assert sc.has_program_scopes(s)


def test_a_module_without_program_scopes_reads_none():
    hlo = (DATA / "ddp_allreduce.25m.hlo.txt").read_text()
    assert not sc.has_program_scopes(sc.instruction_scopes(hlo))


# ---------------------------------------------------------- alignment


CALLS = (0, 1000, 2000)
OFFSETS = {"/device:TPU:0": 300.0, "/device:TPU:1": -450.0}


def aligned_trace():
    """Three calls: dispatch [c, c+50], wait [c+50, c+900].  On the
    host's clock each call's module runs from c+100 to c+600 (the
    second starts as its dispatch does, at c), and its ops as below;
    each device plane's clock reads host time minus its offset."""
    spans, modules, devices = [], {}, {}
    for c in CALLS:
        spans += [ev(tr.CALL, c, c + 900), ev(tr.DISPATCH, c, c + 50),
                  ev(tr.WAIT, c + 50, c + 900)]
    for dev, off in OFFSETS.items():
        mods, ops = [], []
        for k, c in enumerate(CALLS):
            start = c if k == 1 else c + 100
            mods.append(ev("jit_main", start - off, c + 600 - off))
            ops += [ev("fusion.2", c + 100 - off, c + 150 - off),   # split
                    ev("scatter.4", c + 150 - off, c + 350 - off),  # step
                    ev("collective-permute-start", c + 350 - off,
                       c + 352 - off),
                    ev("collective-permute-done", c + 398 - off,
                       c + 400 - off),
                    ev("copy-start", c + 400 - off, c + 420 - off),  # none
                    ev("add.11", c + 420 - off, c + 450 - off),     # loop
                    ev("fusion.10", c + 450 - off, c + 600 - off)]  # join
        modules[dev], devices[dev] = mods, ops
    return tr.Trace(devices=devices, spans=spans, modules=modules)


def test_offsets_recover_known_shifts_and_report_the_width():
    got = align.offsets(aligned_trace())
    assert set(got) == set(OFFSETS)
    for dev, off in OFFSETS.items():
        # the second call pins the lower end (its module starts as its
        # dispatch does); every call returns 300 after its module ends
        assert got[dev].offset == pytest.approx(off)
        assert got[dev].width == pytest.approx(300.0)


def test_offset_refuses_counts_that_differ_and_spans_that_cannot_fit():
    m = [ev("m", 10, 20)]
    assert align.offset(m, [], []) is None
    assert align.offset(m, [ev(tr.DISPATCH, 0, 5)],
                        [ev(tr.WAIT, 5, 30), ev(tr.WAIT, 40, 50)]) is None
    # a wait that ends before a module as long as the call can run
    assert align.offset([ev("m", 0, 100)], [ev(tr.DISPATCH, 0, 5)],
                        [ev(tr.WAIT, 5, 50)]) is None


def test_calls_are_the_read_calls_on_the_host_clock():
    calls = align.calls(aligned_trace())
    assert calls == [align.Call(1000.0, 1000.0, 1600.0, 1900.0),
                     align.Call(2000.0, 2100.0, 2600.0, 2900.0)]
    assert align.calls(tr.Trace(spans=aligned_trace().spans)) == []


def test_new_readers_on_a_synthetic_trace():
    r = reading(aligned_trace(), HLO)
    assert r.calls == 2
    assert read("roundstep_ms", r) == pytest.approx(200e-6)
    assert read("layout_ms", r) == pytest.approx(200e-6)
    assert read("unscoped_ms", r) == pytest.approx(20e-6)
    assert read("roundloop_ms", r) == pytest.approx(30e-6)
    # the four part compute_ms: every op but the permutes
    assert sum(read(m, r) for m in PARTS) == pytest.approx(
        read("compute_ms", r))
    assert read("return_ms", r) == pytest.approx(300e-6)


def test_new_readers_read_nothing_where_there_is_nothing_to_read():
    t = aligned_trace()
    bare = reading(tr.Trace(spans=t.spans), HLO)     # no device plane
    for metric in PARTS + ("return_ms",):
        assert read(metric, bare) is None, metric
    # a program that names none of its work: the scope readers are
    # silent, the return is still read
    unnamed = reading(t, "ENTRY %m (x: f32[8]) -> f32[8] {\n"
                         "  %fusion.2 = f32[8]{0} fusion(%x), kind=kLoop\n}\n")
    for metric in PARTS:
        assert read(metric, unnamed) is None, metric
    assert read("return_ms", unnamed) == pytest.approx(300e-6)
    assert read("return_ms", reading(None)) is None


# ------------------------------- the readers that came before, as they were


def recorded(workload):
    return (tr.load(str(DATA / f"{workload}.xplane.pb")),
            (DATA / f"{workload}.hlo.txt").read_text())


BEFORE = {
    "ddp_allreduce.25m": {
        "compute_ms": 0.016867, "permute_ms": 0.0188135,
        "ops_per_call": 59.875, "idle_share": 98.85860087721406,
        "roundstep_roofline": 14.477989221571363,
        "exchange_roofline": 26.57666037685705, "permutes_per_call": 4.0,
        "dispatch_ms": 2.0},
    "int8_gradsync.4m.rankstack": {
        "compute_ms": 0.042097, "permute_ms": None, "ops_per_call": 108.0,
        "idle_share": 97.90674632810556,
        "roundstep_roofline": 5.800894225247504, "exchange_roofline": None,
        "permutes_per_call": None, "dispatch_ms": 2.0},
}
BREAKDOWN_HEAD = {
    "ddp_allreduce.25m": (["scatter", 4.140000000000001e-06],
                          ["dispatch", 0.001542928]),
    "int8_gradsync.4m.rankstack": (["fusion", 3.0602e-05],
                                   ["dispatch", 0.001860988]),
}


@pytest.mark.parametrize("workload", sorted(BEFORE))
def test_existing_readers_read_the_recorded_traces_as_before(workload):
    t, hlo = recorded(workload)
    r = reading(t, hlo, dispatch=[1e-3, 3e-3, 2e-3], hbm=2e6, ici=1e6)
    for metric, want in BEFORE[workload].items():
        got = read(metric, r)
        assert got == (None if want is None else pytest.approx(want)), metric
    b = harness.breakdown(t)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    op, gap = BREAKDOWN_HEAD[workload]
    assert b["device_ops"][0] == [op[0], pytest.approx(op[1])]
    assert b["idle_gaps"][0] == [gap[0], pytest.approx(gap[1])]


# ------------------------- the scoped program, recorded on the chip


SCOPED = ("ddp_allreduce.25m", "int8_gradsync.4m.rankstack")


@pytest.mark.parametrize("workload", SCOPED)
def test_recorded_scoped_program_parts_its_compute_time(workload):
    t, hlo = recorded(f"{workload}.scoped")
    r = reading(t, hlo)
    parts = {m: read(m, r) for m in PARTS}
    assert all(v is not None and v >= 0 for v in parts.values()), parts
    assert parts["roundstep_ms"] > 0 and parts["layout_ms"] > 0
    # ops of different parts may overlap in time, never by much
    assert sum(parts.values()) == pytest.approx(read("compute_ms", r),
                                                rel=0.02)
    offs = align.offsets(t)
    assert set(offs) == set(t.devices)
    assert all(o.width >= 0 for o in offs.values())
    assert 0 < read("return_ms", r) < 5


def test_recorded_plan_calls_write_nested_program_spans():
    """The allreduce's plan calls on the chip: one ``circulant.call``
    per benchmark call, with one validate and one execute inside."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(
        str(DATA / "ddp_allreduce.25m.scoped.xplane.pb"))
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(("circulant.", "bench."))]
    outer = [s for s in spans if s[0] == "circulant.call"]
    assert len(outer) == sum(s[0] == tr.CALL for s in spans) == 3
    for _, a, b in outer:
        for inner in ("circulant.validate", "circulant.execute"):
            assert sum(s[0] == inner and a <= s[1] and s[2] <= b
                       for s in spans) == 1
