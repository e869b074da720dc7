#!/usr/bin/env python3
"""Record a small device trace and compiled HLO of one cell, at a
reduced payload, for the reducers' tests; and print the trace's layout
(planes, lines, event counts and names) to read it by hand.  Runs on
the chip:

    python3 bench/tests/record_fixture.py --workload <name> \
        --bucket-bytes 65536 --calls 2 --out <dir>

writes ``<dir>/<workload>.xplane.pb`` and ``<dir>/<workload>.hlo.txt``.
"""

import argparse
import json
import shutil
import sys
import tempfile
import time
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def describe(path: str) -> None:
    """Print every plane and line of a trace with counts and samples."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r} lines={len(lines)}")
        for line in lines:
            evs = list(line.events)
            if not evs:
                continue
            names = Counter(e.name for e in evs)
            lo = min(e.start_ns for e in evs)
            hi = max(e.start_ns + e.duration_ns for e in evs)
            print(f"  LINE {line.name!r} events={len(evs)} "
                  f"span_ns=[{lo}, {hi}] top={names.most_common(8)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--bucket-bytes", type=int, required=True)
    ap.add_argument("--calls", type=int, default=2)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax

    from bench import harness
    from bench import trace as tr

    cell = harness.load_cell(args.workload)
    cell.config = {**cell.config, "bucket_bytes": args.bucket_bytes}
    job = cell.entry.Job(cell.config, cell.traffic,
                         jax.devices()[:cell.chips], args.seed)
    for i in range(cell.traffic["warm_calls"]):
        jax.block_until_ready(job.issue(i))
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{args.workload}.hlo.txt").write_text(job.hlo_text())
    with tempfile.TemporaryDirectory() as tmp:
        samples = harness.Reservoir(1, args.seed)
        t0 = time.perf_counter()
        _, path = tr.capture(tmp, lambda: harness.closed_loop(
            job, 0, samples, calls=args.calls, annotate=True))
        print(f"traced {args.calls} calls in {time.perf_counter() - t0} s")
        describe(path)
        dest = out / f"{args.workload}.xplane.pb"
        shutil.copy(path, dest)
    t = tr.load(str(dest))
    print(json.dumps({"devices": {k: len(v) for k, v in t.devices.items()},
                      "spans": len(t.spans), "window": t.window(),
                      "bytes": dest.stat().st_size}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
