"""The harness finds a cell's files by the names in BENCHMARK.json: a
configuration, a traffic mix, a cell and a per-layer metric added as new
files and entries run with no edit to a file that was there."""

import json
import shutil
import subprocess
import sys
import time

import pytest

from bench import harness

ROOT = harness.ROOT


def test_added_config_traffic_cell_and_metric_are_found(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads(
        (ROOT / "bench/configs/ddp_allreduce_f32.json").read_text())
    cfg.update(name="tiny_allreduce", bucket_bytes=4 * 4096)
    (tmp_path / "bench/configs/tiny_allreduce.json").write_text(
        json.dumps(cfg))
    shutil.copy(ROOT / "bench/configs/ddp_allreduce_f32_ref.py",
                tmp_path / "bench/configs/tiny_allreduce_ref.py")
    traffic = json.loads(
        (ROOT / "bench/traffic/closed_loop.mesh.ints.json").read_text())
    traffic.update(ring=2, trace_calls=5, sample_calls=2)
    (tmp_path / "bench/traffic/tiny.mesh.json").write_text(json.dumps(traffic))
    (tmp_path / "bench/metrics/traced_calls.py").write_text(
        "def read(r):\n    return float(r.calls) if r.calls else None\n")
    bench["configs"].append({"name": "tiny_allreduce", "source": "test",
                             "file": "bench/configs/tiny_allreduce.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.cell", "config": "tiny_allreduce",
                               "traffic": "tiny.mesh", "chips": 4,
                               "why": "test"})
    bench["per_layer"].append({"name": "traced_calls", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "round loop", "moves": "busbw",
                               "workloads": ["tiny.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell("tiny.cell", root=tmp_path)
    assert cell.config["name"] == "tiny_allreduce"
    # the metrics of the first cells list their own cells only
    assert set(cell.readers) == {"traced_calls"}
    res = harness.run(cell, 5, 0.2, True, time.perf_counter(),
                      require_tpu=False)
    assert res["correct"] is True
    # five traced calls, of which the first (the profiler's start) is
    # not read
    assert res["metrics"]["traced_calls"] == {"value": 4.0, "unit": "count"}
    assert list(res)[-1] == "checks"


def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.BenchError):
        harness.load_peaks("TPU v99")
    assert harness.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_run_off_tpu_exits_nonzero_and_prints_no_result():
    res = subprocess.run(
        [sys.executable, str(ROOT / "bench/run.py"), "--workload",
         "int8_gradsync.4m.rankstack", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert res.returncode != 0
    assert "needs a TPU" in res.stderr
    assert not [ln for ln in res.stdout.splitlines() if ln.startswith("{")]
