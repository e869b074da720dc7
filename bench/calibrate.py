#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from, on the chip,
at the cell's own size: for each seed, a short closed loop of the
program, the reference's numbers over as many sampled calls as a run
compares (the lower readings), and the same numbers for the control --
the plain reference one precision below the stated ones, in the
program's place, on the same calls' inputs (the upper readings).

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 [--seconds 2]

Prints one JSON line per seed, then one with the largest program
reading and the smallest control reading of each number.  Benchmark
runs never run the control.
"""

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(cell, job, samples):
    """Worst program and control numbers over the sampled chains."""
    prog, ctrl = {}, {}
    for rec in samples:
        s = job.fetch(rec)
        for out, got in ((prog, cell.reference.numbers(s, cell.config)),
                         (ctrl, cell.reference.numbers(
                             cell.reference.control(s, cell.config),
                             cell.config))):
            for k, v in got.items():
                out[k] = max(out.get(k, 0.0), v)
    return prog, ctrl


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)

    from bench.run import setup_compile_cache

    setup_compile_cache()
    import jax

    from bench import harness

    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"calibrate: needs {cell.chips} TPU chips", file=sys.stderr)
        return 2
    lower, upper = {}, {}
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        job = cell.entry.Job(cell.config, cell.traffic,
                             devices[:cell.chips], seed)
        for i in range(cell.traffic["warm_calls"]):
            jax.block_until_ready(job.issue(i))
        samples = harness.sampler(cell.traffic, seed)
        win = harness.closed_loop(job, 0, samples, seconds=args.seconds)
        prog, ctrl = readings(cell, job, samples.items)
        print(json.dumps({"seed": seed, "calls": win.calls,
                          "sampled": len(samples.items), "program": prog,
                          "control": ctrl,
                          "seconds": time.perf_counter() - t0}), flush=True)
        for k, v in prog.items():
            lower[k] = max(lower.get(k, 0.0), v)
        for k, v in ctrl.items():
            upper[k] = min(upper.get(k, float("inf")), v)
        del job, samples
        gc.collect()
    print(json.dumps({"workload": cell.name, "lower": lower,
                      "upper": upper, "limits": cell.config["limits"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
