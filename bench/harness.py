"""The harness: finds a cell's files by name and runs one measured run.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it:

* ``configs[].file``: the configuration's sizes, its ``entry``, and the
  ``limits`` of its correctness numbers; beside it ``<config>_ref.py``,
  its plain reference (NumPy; imports nothing of the program) with the
  comparison ``numbers(sample, config)`` and the lower-precision
  ``control(sample, config)``, where a sample is ``Job.fetch`` of one
  sampled chain of consecutive calls;
* ``bench/entries/<entry>.py``: a ``Job`` that drives the program;
* ``bench/traffic/<traffic>.json``: the traffic mix, read by the one
  generator in ``bench/data.py`` and by the closed loop here;
* ``bench/metrics/<metric>.py``: a per-layer reader,
  ``read(Reading) -> float | None``.

A run: set-up (device, data, plan, compile, warm-up, XLA baseline),
then a closed loop with one caller for ``--seconds``, then with
``--trace 1`` a short profiled window, then the reference over a sample
of chains of consecutive calls drawn from the seed.
"""

from __future__ import annotations

import importlib.util
import json
import random
import statistics
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

from bench import trace as tr
from bench.reading import Reading

ROOT = Path(__file__).resolve().parent.parent


class BenchError(RuntimeError):
    """The run cannot be made here (no chip, too few chips, unknown
    device kind); nothing is printed on standard output."""


# ------------------------------------------------------------ discovery


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    entry: Any
    reference: Any
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    readers: Dict[str, Any] = field(default_factory=dict)


def _for_cell(metrics, cell):
    return [m for m in metrics
            if "workloads" not in m or cell in m["workloads"]]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Resolve a cell of ``root/BENCHMARK.json`` to its files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(work)})")
    w = work[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg_path = root / cfg["file"]
    config = json.loads(cfg_path.read_text())
    safe = name.replace(".", "_").replace("-", "_")
    per_layer = _for_cell(bench["per_layer"], name)
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    if (traffic["loop"], traffic["callers"]) != ("closed", 1):
        raise ValueError(f"traffic {w['traffic']!r}: the harness runs a "
                         "closed loop with one caller")
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        entry=load_module(root / "bench" / "entries" / f"{config['entry']}.py",
                          f"bench_entry_{safe}"),
        reference=load_module(cfg_path.with_name(f"{w['config']}_ref.py"),
                              f"bench_ref_{safe}"),
        end_to_end=_for_cell(bench["end_to_end"], name),
        per_layer=per_layer,
        readers={m["name"]: load_module(
            root / "bench" / "metrics" / f"{m['name']}.py",
            f"bench_metric_{m['name'].replace('.', '_')}")
            for m in per_layer},
    )


def load_peaks(device_kind: str, root: Path = ROOT) -> Dict[str, float]:
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise BenchError(f"device kind {device_kind!r} is not in "
                         f"bench/peaks.json ({sorted(table['devices'])})")
    return table["devices"][device_kind]


# ------------------------------------------------------------ the loop


class Reservoir:
    """A uniform sample of ``k`` chains of ``chain`` consecutive call
    records from a stream of unknown length, drawn from the seed
    (Algorithm R over the stream cut into chains).  Each item is a tuple
    of records in call order, so a stateful entry's reference can feed
    each call the state that the previous call of its chain returned."""

    def __init__(self, k: int, seed: int, chain: int = 1):
        self.k, self.chain, self.seen, self.items = k, chain, 0, []
        self._open: List[Any] = []
        self._rng = random.Random(seed)

    def offer(self, record):
        self._open.append(record)
        if len(self._open) < self.chain:
            return
        item, self._open = tuple(self._open), []
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = self._rng.randrange(self.seen + 1)
            if j < self.k:
                self.items[j] = item
        self.seen += 1


def sampler(traffic: Dict[str, Any], seed: int) -> Reservoir:
    """The traffic's sample: ``sample_calls`` calls in all, in chains of
    ``chain_calls`` (default 1) consecutive calls."""
    chain = int(traffic.get("chain_calls", 1))
    return Reservoir(traffic["sample_calls"] // chain, seed, chain)


@dataclass
class Window:
    calls: int = 0
    seconds: float = 0.0
    latency_s: List[float] = field(default_factory=list)
    dispatch_s: List[float] = field(default_factory=list)


def closed_loop(job, first: int, samples: Reservoir, *,
                seconds: Optional[float] = None, calls: Optional[int] = None,
                annotate: bool = False) -> Window:
    """One caller: issue a call, wait until it is ready, issue the next.
    Runs for ``seconds`` or for ``calls`` calls."""
    import jax

    win = Window()
    if annotate:
        def span(name):
            return jax.profiler.TraceAnnotation(name)
    else:
        from contextlib import nullcontext

        def span(name):
            return nullcontext()
    t_start = time.perf_counter()
    t_stop = t_start + seconds if seconds is not None else None
    i = first
    while True:
        t0 = time.perf_counter()
        if t_stop is not None and t0 >= t_stop:
            break
        if calls is not None and win.calls >= calls:
            break
        with span(tr.CALL):
            with span(tr.DISPATCH):
                out = job.issue(i)
            t1 = time.perf_counter()
            with span(tr.WAIT):
                jax.block_until_ready(out)
        t2 = time.perf_counter()
        win.latency_s.append(t2 - t0)
        win.dispatch_s.append(t1 - t0)
        samples.offer(job.record(i, out))
        win.calls += 1
        i += 1
    win.seconds = time.perf_counter() - t_start
    return win


def busbw(calls: int, payload_bytes: int, p: int, seconds: float) -> float:
    """nccl-tests bus bandwidth in GB/s: calls x S x 2(p-1)/p / time."""
    return calls * payload_bytes * 2 * (p - 1) / p / seconds / 1e9


def p95(values: List[float]) -> float:
    import numpy as np

    return float(np.percentile(np.asarray(values), 95))


class CompileCounter:
    """Counts the compile requests JAX makes (persistent-cache hits and
    misses alike) through ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event, **kwargs):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def close(self):
        import jax

        jax.monitoring.unregister_event_listener(self._on)


# ---------------------------------------------------------- reductions


def breakdown(trace: tr.Trace) -> Dict[str, list]:
    """The device ops that took most time (mean seconds per device over
    the traced window, copies of one op merged by name), and the longest
    idle gaps, each named by the benchmark span open at its middle."""
    lo, hi = trace.window()
    n = max(1, len(trace.devices))
    per_op: Dict[str, float] = {}
    idle = []
    inner = [s for s in trace.spans if s.name in (tr.DISPATCH, tr.WAIT)]
    for ops in trace.devices.values():
        busy = []
        for e in ops:
            a, b = max(e.start, lo), min(e.end, hi)
            if b > a:
                k = tr.base_name(e.name)
                per_op[k] = per_op.get(k, 0.0) + (b - a) / 1e9 / n
                busy.append((a, b))
        for a, b in tr.gaps(tr.union(busy), lo, hi):
            mid = (a + b) / 2
            label = next((s.name.split(".", 1)[1] for s in inner
                          if s.start <= mid <= s.end), "between calls")
            idle.append([label, (b - a) / 1e9])
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    idle.sort(key=lambda g: -g[1])
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": idle[:10]}


def check(cell: Cell, job, samples: List[Any]):
    """The reference's numbers, worst over the sampled chains of calls,
    each with its limit (a number that is not finite reads as infinity),
    and how many sampled chains failed a limit."""
    import math

    worst: Dict[str, float] = {}
    failed = 0
    limits = cell.config["limits"]
    for rec in samples:
        got = cell.reference.numbers(job.fetch(rec), cell.config)
        if any(not (got[k] <= limits[k]) for k in limits):
            failed += 1
        for k, v in got.items():
            v = float(v) if math.isfinite(v) else math.inf
            worst[k] = max(worst.get(k, 0.0), v)
    return {k: {"value": worst.get(k, math.inf), "limit": float(limits[k])}
            for k in limits}, failed


# ----------------------------------------------------------------- run


def run(cell: Cell, seed: int, seconds: float, trace: bool, t0: float, *,
        require_tpu: bool = True, out=sys.stdout,
        err=sys.stderr) -> Dict[str, Any]:
    """One run of ``cell``; returns the result object (also printed as
    the last line of ``out``).  Raises :class:`BenchError` before any
    output when the chips are not there."""
    import jax

    def log(msg):
        print(f"[bench] {msg}", file=err, flush=True)

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise BenchError(f"needs a TPU, JAX found {dev.platform!r}")
    if len(devices) < cell.chips:
        raise BenchError(f"cell {cell.name} needs {cell.chips} chips, JAX "
                         f"found {len(devices)}")
    peaks = load_peaks(dev.device_kind) if require_tpu else {}
    used = devices[:cell.chips]
    compiles = CompileCounter()
    traffic = cell.traffic

    phases = {"start_to_devices": time.perf_counter() - t0}
    t = time.perf_counter()
    job = cell.entry.Job(cell.config, traffic, used, seed)
    jax.block_until_ready(job.ring)
    phases["data_and_plan"] = time.perf_counter() - t
    log(f"cell={cell.name} device={dev.device_kind} count={len(devices)} "
        f"{job.describe()}")
    t = time.perf_counter()
    for i in range(traffic["warm_calls"]):
        jax.block_until_ready(job.issue(i))
    phases["compile_and_warm"] = time.perf_counter() - t
    t = time.perf_counter()
    base = _baseline(job, traffic["baseline_calls"])
    phases["xla_baseline"] = time.perf_counter() - t
    print(json.dumps({"xla_baseline": base}), file=out, flush=True)
    log(f"xla_baseline {base}")
    setup_s = time.perf_counter() - t0
    log(f"setup_s={setup_s} {phases} compile_requests={compiles.requests} "
        f"cache_hits={compiles.hits}")

    samples = sampler(traffic, seed)
    before = compiles.requests
    win = closed_loop(job, 0, samples, seconds=seconds)
    in_window = compiles.requests - before
    compiles.close()
    log(f"window calls={win.calls} seconds={win.seconds} "
        f"compiles_in_window={in_window}")

    traced = None
    hlo = ""
    if trace:
        hlo = job.hlo_text()
        with tempfile.TemporaryDirectory() as tmp:
            tw, path = tr.capture(tmp, lambda: closed_loop(
                job, win.calls, samples, calls=traffic["trace_calls"],
                annotate=True))
            traced = tr.load(path)
        log(f"traced calls={tw.calls} median_latency_s="
            f"{statistics.median(tw.latency_s)} (untraced "
            f"{statistics.median(win.latency_s)})")

    mem = [d.memory_stats() or {} for d in used]
    memory_peak = max(m.get("peak_bytes_in_use", 0) for m in mem)

    t_check = time.perf_counter()
    checks, failed = check(cell, job, samples.items)
    log(f"reference over {len(samples.items)} sampled chains of "
        f"{samples.chain} calls took "
        f"{time.perf_counter() - t_check} s")
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    attempted = win.calls + (traffic["trace_calls"] if trace else 0)

    metrics: Dict[str, Dict[str, Any]] = {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    spent = None
    if not trace:
        values = {
            "busbw": busbw(win.calls, job.payload_bytes, job.p, win.seconds),
            "lat_p95_ms": p95(win.latency_s) * 1e3,
            "setup_s": setup_s,
        }
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        reading = Reading(trace=traced, hlo=hlo, dispatch_s=win.dispatch_s,
                          least_hbm_bytes=job.least_hbm_bytes,
                          least_ici_bytes=job.least_ici_bytes, peaks=peaks)
        for m in cell.per_layer:
            v = cell.readers[m["name"]].read(reading)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        busy = reading.per_device(lambda ops, lo, hi: tr.length(tr.clip(
            tr.union((e.start, e.end) for e in ops), lo, hi)),
            host_window=True)
        w = traced.window()
        device["busy_s"] = (busy or 0.0) / 1e9
        device["window_s"] = (w[1] - w[0]) / 1e9 if w else 0.0
        if traced.devices and w:
            spent = breakdown(traced)
    result: Dict[str, Any] = {"correct": correct, "attempted": attempted,
                              "failed": failed, "metrics": metrics,
                              "device": device}
    if spent:
        result["breakdown"] = spent
    result["checks"] = checks
    print(json.dumps(result), file=out, flush=True)
    for k, c in checks.items():
        print(f"[check] {k}={c['value']!r} limit={c['limit']!r}", file=err,
              flush=True)
    return result


def _baseline(job, calls: int) -> Dict[str, float]:
    """XLA's own collective on the same data, closed loop, one caller."""
    import jax

    for i in range(2):
        jax.block_until_ready(job.baseline(i))
    lat = []
    t_start = time.perf_counter()
    for i in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(job.baseline(i))
        lat.append(time.perf_counter() - t0)
    total = time.perf_counter() - t_start
    return {"calls": calls,
            "lat_median_ms": statistics.median(lat) * 1e3,
            "lat_p95_ms": p95(lat) * 1e3,
            "busbw": busbw(calls, job.payload_bytes, job.p, total)}
