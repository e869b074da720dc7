#!/usr/bin/env python3
"""One measured run of one benchmark cell, from the root of a checkout:

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell's chips must be there: off TPU, or with fewer chips than the
cell asks for, the run exits 2 and prints no result.  JAX's persistent
compilation cache lives in ``$JAX_COMPILATION_CACHE_DIR`` when that is
set, and otherwise in ``.jax_cache/`` at the checkout's root, so only
the first run of a cell compiles.

Standard output ends with one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number the reference compared,
beside its limit.  An earlier line holds XLA's own collective on the
same data (``xla_baseline``).  Standard error ends with the checks.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    setup_compile_cache()
    from bench import harness

    cell = harness.load_cell(args.workload)
    try:
        harness.run(cell, args.seed, args.seconds, bool(args.trace), T0)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
