"""The trace and HLO reducers, on synthetic events, on small traces and
compiled HLO recorded on the chip (``bench/tests/data``), and on HLO
compiled for a described v5e 2x2 host."""

from pathlib import Path

import pytest

from bench import harness
from bench import trace as tr
from bench.hlo import collective_stats, fusion_count, permute_done_to_start
from bench.reading import Reading

DATA = Path(__file__).parent / "data"
PEAKS = {"hbm_bytes_per_s": 819e9, "ici_bytes_per_s": 200e9}


def ev(name, a, b):
    return tr.Event(name, float(a), float(b))


def reading(trace, hlo="", dispatch=(), hbm=0.0, ici=0.0):
    return Reading(trace=trace, hlo=hlo, dispatch_s=list(dispatch),
                   least_hbm_bytes=hbm, least_ici_bytes=ici, peaks=PEAKS)


def read(metric, r):
    return harness.load_module(
        harness.ROOT / "bench" / "metrics" / f"{metric}.py",
        f"test_metric_{metric}").read(r)


# ------------------------------------------------------------ intervals


def test_union_merges_overlaps_and_drops_empty():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (8, 8)]) == [
        (0, 4), (5, 7)]


def test_clip_gaps_and_length():
    merged = [(0, 4), (5, 7), (9, 12)]
    assert tr.clip(merged, 2, 10) == [(2, 4), (5, 7), (9, 10)]
    assert tr.length(tr.clip(merged, 2, 10)) == 5
    assert tr.gaps(merged, 2, 10) == [(4, 5), (7, 9)]
    assert tr.gaps([], 1, 3) == [(1, 3)]


def test_permute_intervals_pair_each_done_with_its_start():
    # two permutes in flight at once; the HLO names each done's start
    ops = [ev("collective-permute-start", 0, 1),
           ev("collective-permute-start.1", 2, 3),
           ev("fusion.4", 3, 6),
           ev("collective-permute-done.1", 7, 8),
           ev("collective-permute-done", 9, 10)]
    pairs = {"collective-permute-done": "collective-permute-start",
             "collective-permute-done.1": "collective-permute-start.1"}
    assert sorted(tr.permute_intervals(ops, pairs)) == [(0, 10), (2, 8)]
    # without the HLO's names a done takes the oldest open start
    assert sorted(tr.permute_intervals(ops, {})) == [(0, 8), (2, 10)]
    assert tr.union(tr.permute_intervals(ops, {})) == [(0, 10)]


CALLS = (0, 100, 200)


def synthetic_trace():
    """Three calls on two devices: a fusion, then an exchange in flight
    from 20 to 60 (start and done events of 2 ns), a second fusion.  The
    first call is not read (it pays for the profiler's start)."""
    def dev(shift):
        ops = []
        for c in CALLS:
            ops += [ev("fusion.1", c + 10 + shift, c + 20 + shift),
                    ev("collective-permute-start", c + 20 + shift,
                       c + 22 + shift),
                    ev("collective-permute-done", c + 58 + shift,
                       c + 60 + shift),
                    ev("copy_fusion.3", c + 60 + shift, c + 70 + shift)]
        return ops

    spans = []
    for c in CALLS:
        spans += [ev(tr.CALL, c, c + 90), ev(tr.DISPATCH, c, c + 5),
                  ev(tr.WAIT, c + 5, c + 90)]
    return tr.Trace(devices={"/device:TPU:0": dev(0), "/device:TPU:1":
                             dev(2)}, spans=spans)


def test_metrics_on_a_synthetic_trace():
    t = synthetic_trace()
    hlo = ("%collective-permute-done = f32[8]{0} collective-permute-done("
           "%collective-permute-start)\n")
    r = reading(t, hlo, dispatch=[1e-3, 3e-3, 2e-3], hbm=2e4, ici=8e3)
    assert t.window() == (100.0, 290.0) and r.calls == 2
    # fusion 10 ns + copy 10 ns per call; permute events do not count
    assert read("compute_ms", r) == pytest.approx(20e-6)
    assert read("permute_ms", r) == pytest.approx(40e-6)
    assert read("ops_per_call", r) == pytest.approx(4.0)
    # busy 24 ns per call of 190 / 2 -> 48 of 190 on each device
    assert read("idle_share", r) == pytest.approx((1 - 48 / 190) * 100)
    assert read("dispatch_ms", r) == pytest.approx(2.0)
    assert read("roundstep_roofline", r) == pytest.approx(
        2e4 / 819e9 / 20e-9 * 100)
    assert read("exchange_roofline", r) == pytest.approx(
        8e3 / 200e9 / 40e-9 * 100)


def test_readers_return_nothing_without_something_to_read():
    t = synthetic_trace()
    bare = reading(tr.Trace(spans=t.spans))          # no device plane
    for metric in ("compute_ms", "permute_ms", "ops_per_call",
                   "roundstep_roofline", "exchange_roofline",
                   "permutes_per_call", "dispatch_ms"):
        assert read(metric, bare) is None, metric
    no_permutes = tr.Trace(devices={"/device:TPU:0": [
        ev("fusion", 30, 40)]}, spans=[ev(tr.CALL, 0, 20),
                                       ev(tr.CALL, 25, 45)])
    r = reading(no_permutes, hbm=1.0, ici=1.0)
    assert read("permute_ms", r) is None
    assert read("exchange_roofline", r) is None
    assert read("compute_ms", r) == pytest.approx(10e-6)


def test_breakdown_names_ops_and_labels_gaps():
    b = harness.breakdown(synthetic_trace())
    names = [n for n, _ in b["device_ops"]]
    assert set(names) == {"fusion", "copy_fusion", "collective-permute-start",
                          "collective-permute-done"}
    assert len(b["idle_gaps"]) <= 10
    labels = {g[0] for g in b["idle_gaps"]}
    assert labels <= {"dispatch", "wait", "between calls"}
    assert "wait" in labels and "between calls" in labels


# ---------------------------------------------- recorded on the chip


def recorded(workload):
    pb = DATA / f"{workload}.xplane.pb"
    hlo = DATA / f"{workload}.hlo.txt"
    if not pb.exists():
        pytest.fail(f"missing fixture {pb}")
    return tr.load(str(pb)), hlo.read_text()


def test_instruction_names_and_opcodes():
    assert tr.instruction(
        "%while.72 = (s32[]{:T(128)}, s8[4,1,6,209920]{3,2,1,0:T(8,128)"
        "(4,1)S(1)}, /*index=5*/s32[]) while(%tuple.3), condition=%c") == (
        "while.72", "while")
    assert tr.instruction(
        "%collective-permute-start.1 = (f32[1,8]{1,0:T(1,128)S(1)}, "
        "f32[1,8]{1,0}, u32[]{:S(2)}) collective-permute-start(%gte)") == (
        "collective-permute-start.1", "collective-permute-start")
    assert tr.instruction("fusion.12") == ("fusion.12", "fusion")


def test_recorded_ddp_trace_four_devices_async_permutes():
    t, hlo = recorded("ddp_allreduce.25m")
    assert len(t.devices) == 4 and t.calls() == 2
    n = collective_stats(hlo).ops_by_kind["collective-permute"]
    pairs = permute_done_to_start(hlo)
    assert len(pairs) == n
    for dev, ops in t.devices.items():
        # the device's clock runs up to 0.6 ms off the host's here, so
        # the read calls are found by the device's own modules
        lo, hi = t.device_window(dev)
        assert (lo, hi) == (t.modules[dev][1].start, t.modules[dev][2].end)
        starts = [e for e in ops if e.name.startswith(tr.PERMUTE_START)
                  and lo <= e.start <= hi]
        assert len(starts) == 2 * n
        spans = tr.permute_intervals(ops, pairs)
        assert len(spans) == 3 * n          # the first call's too
        assert all(b > a for a, b in spans)
    r = reading(t, hlo, hbm=1.0, ici=1.0)
    assert 0 < read("permute_ms", r) < 1e3
    assert 0 < read("compute_ms", r) < 1e3
    assert 0 < read("idle_share", r) < 100


def test_recorded_rankstack_trace_one_device_no_permutes():
    t, hlo = recorded("int8_gradsync.4m.rankstack")
    assert len(t.devices) == 1 and t.calls() == 2
    assert "collective-permute" not in collective_stats(hlo).ops_by_kind
    r = reading(t, hlo, hbm=1.0)
    assert read("permute_ms", r) is None
    # each call runs at least the fusions of its executable's entry
    assert read("ops_per_call", r) > 0.5 * fusion_count(hlo)
    assert 0 < read("idle_share", r) < 100


def test_collective_stats_counts_async_tuple_shaped_permutes():
    hlo = """HloModule m

ENTRY %main (p: f32[1,8]) -> f32[1,8] {
  %p = f32[1,8]{1,0} parameter(0)
  %collective-permute-start = (f32[1,8]{1,0:T(1,128)S(1)}, f32[1,8]{1,0:T(1,128)S(1)}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%p), channel_id=1, source_target_pairs={{0,1},{1,0}}
  %collective-permute-done = f32[1,8]{1,0:T(1,128)S(1)} collective-permute-done(%collective-permute-start)
  %fusion.1 = f32[1,8]{1,0} fusion(%collective-permute-done), kind=kLoop, calls=%fc
  ROOT %collective-permute.2 = f32[1,8]{1,0} collective-permute(%fusion.1), source_target_pairs={{0,1},{1,0}}
}
"""
    stats = collective_stats(hlo)
    assert stats.ops_by_kind == {"collective-permute": 2}
    assert stats.bytes_by_kind == {"collective-permute": 2 * 8 * 4}
    assert permute_done_to_start(hlo) == {
        "collective-permute-done": "collective-permute-start"}
    assert fusion_count(hlo) == 1
