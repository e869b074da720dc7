"""Entry: the library's f32 allreduce as a user calls it.

``CirculantComm(mesh, "x").plan("allreduce", x)`` with the library's
defaults (block count from its cost model, ``jnp`` round step, no
overlap), then ``plan(x)`` per call.  One rank per chip (``layout:
"mesh"``).  XLA's baseline is ``psum`` under ``shard_map`` on the same
data.
"""

from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench.data import make_ring


def program(config, traffic, devices):
    """The timed callable, the shapes of its arguments and the job's
    least bytes; ``devices`` may be described ones (AOT compile)."""
    from repro.core.comm import CirculantComm

    if traffic["layout"] != "mesh":
        raise ValueError("circulant_allreduce runs one rank per chip "
                         f"(layout 'mesh'), not {traffic['layout']!r}")
    p = config["ranks"]
    elems = config["bucket_bytes"] // 4
    mesh = Mesh(np.array(devices[:p]), ("x",))
    sharding = NamedSharding(mesh, P("x"))
    x = jax.ShapeDtypeStruct((p, elems), jnp.float32, sharding=sharding)
    plan = CirculantComm(mesh, "x").plan("allreduce", x)
    return SimpleNamespace(
        fn=plan, args=(x,), sharding=sharding, p=p,
        payload_bytes=elems * 4,
        # each rank reads its input and writes its output once
        least_hbm_bytes=2 * elems * 4,
        least_ici_bytes=2 * (p - 1) / p * elems * 4,
        baseline=jax.jit(jax.shard_map(
            lambda a: jax.lax.psum(a, "x"), mesh=mesh, in_specs=P("x"),
            out_specs=P("x"))),
        describe=plan.describe())


class Job:
    def __init__(self, config, traffic, devices, seed):
        prog = program(config, traffic, devices)
        self.p, self.payload_bytes = prog.p, prog.payload_bytes
        self.least_hbm_bytes = prog.least_hbm_bytes
        self.least_ici_bytes = prog.least_ici_bytes
        self.fn, self._prog = prog.fn, prog
        self.ring = make_ring(traffic["values"], prog.args[0].shape,
                              prog.sharding, traffic["ring"], seed)

    def describe(self) -> str:
        return self._prog.describe

    def issue(self, i):
        return self.fn(self.ring[i % len(self.ring)])

    def record(self, i, out):
        return (i % len(self.ring), out)

    def fetch(self, chain):
        # calls are independent: a chain of one
        (idx, out), = chain
        return {"x": np.asarray(self.ring[idx]), "out": np.asarray(out)}

    def baseline(self, i):
        return self._prog.baseline(self.ring[i % len(self.ring)])

    def hlo_text(self) -> str:
        return jax.jit(self._prog.fn).lower(self.ring[0]).compile().as_text()
