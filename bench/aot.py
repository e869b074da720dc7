#!/usr/bin/env python3
"""Compile each cell's timed program for a described TPU v5e 2x2 host,
with no chip attached, and print what the compiler reports: memory per
device, collective-permutes and fusions in the executable, and the
compile's wall time on this host.  Counts, never timings of the chip.

    JAX_PLATFORMS=cpu python3 bench/aot.py [workload ...]
"""

import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def compile_cell(name, topo):
    """``(compiled executable, compile seconds)`` of one cell's timed
    program on the described devices."""
    import jax

    from bench.harness import load_cell

    cell = load_cell(name)
    prog = cell.entry.program(cell.config, cell.traffic,
                              topo.devices[:cell.chips])
    t0 = time.perf_counter()
    compiled = jax.jit(prog.fn).lower(*prog.args).compile()
    return compiled, time.perf_counter() - t0


def main(argv=None) -> int:
    import json

    import jax
    from jax.experimental import topologies

    from bench.hlo import collective_stats, fusion_count

    jax.config.update("jax_enable_compilation_cache", False)
    names = argv if argv else [w["name"] for w in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["workloads"]]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in names:
        compiled, secs = compile_cell(name, topo)
        text = compiled.as_text()
        mem = compiled.memory_analysis()
        print(json.dumps({
            "workload": name,
            "compile_s_on_this_host": secs,
            "collective_permutes": collective_stats(text).ops_by_kind.get(
                "collective-permute", 0),
            "fusions": fusion_count(text),
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "generated_code_bytes": mem.generated_code_size_in_bytes,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
