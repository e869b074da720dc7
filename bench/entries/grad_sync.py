"""Entry: the trainer's ``grad_sync='compressed'`` gradient sync, in
either layout.

The trainer's shard body, ``repro.optim.compression.
compressed_grad_sync`` on a one-bucket spec, with the library's defaults
(block count from its cost model, ``jnp`` round step).  The error state
is carried from call to call as the trainer carries ``gsync_err``.

* ``layout: "mesh"``: one rank per chip, as the trainer's
  ``_make_compressed_step`` runs it, ``jax.jit(jax.shard_map(body))``
  over ``[p, elems]`` gradients and error state sharded on ``"x"``; the
  exchange is ICI permutes.  XLA's baseline is ``psum / p`` under the
  same ``shard_map``.
* ``layout: "rankstack"``: the ``compressed_grad_sync`` entry's program
  and job, the p ranks stacked on one chip under ``vmap``.
"""

from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench.data import make_ring
from bench.entries import compressed_grad_sync as stacked


def program(config, traffic, devices):
    """The timed callable, the shapes of its arguments and the job's
    least bytes; ``devices`` may be described ones (AOT compile)."""
    if traffic["layout"] == "rankstack":
        return stacked.program(config, traffic, devices)
    if traffic["layout"] != "mesh":
        raise ValueError("grad_sync runs layout 'mesh' or 'rankstack', not "
                         f"{traffic['layout']!r}")
    from repro.optim.compression import compressed_grad_sync, make_bucket_spec

    p = config["ranks"]
    elems = config["bucket_bytes"] // 4
    spec = make_bucket_spec(jax.ShapeDtypeStruct((elems,), jnp.float32),
                            config["bucket_bytes"])
    mesh = Mesh(np.array(devices[:p]), ("x",))

    def body(g, e):
        mean, errs = compressed_grad_sync(g[0], [e[0]], "x", p, spec)
        return mean[None], errs[0][None]

    sharding = NamedSharding(mesh, P("x"))
    x = jax.ShapeDtypeStruct((p, elems), jnp.float32, sharding=sharding)
    scales = elems // config["qblock"] * 4
    return SimpleNamespace(
        fn=jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("x"), P("x")),
                                 out_specs=(P("x"), P("x")))),
        args=(x, x), sharding=sharding, p=p, payload_bytes=elems * 4,
        # each chip reads its gradient and error, writes mean and error
        least_hbm_bytes=4 * elems * 4,
        # an allreduce of the int8 payload and its f32 scales
        least_ici_bytes=2 * (p - 1) / p * (elems + scales),
        baseline=jax.jit(jax.shard_map(
            lambda g: jax.lax.psum(g, "x") / p, mesh=mesh, in_specs=P("x"),
            out_specs=P("x"))),
        describe=f"compressed_grad_sync p={p} layout=mesh "
                 f"bytes_per_rank={elems * 4}")


class Job(stacked.Job):
    """The rank stack's job (error state carried from call to call,
    chains fetched for the reference) on this entry's program."""

    def __init__(self, config, traffic, devices, seed):
        prog = program(config, traffic, devices)
        self.p, self.payload_bytes = prog.p, prog.payload_bytes
        self.least_hbm_bytes = prog.least_hbm_bytes
        self.least_ici_bytes = prog.least_ici_bytes
        self.fn, self._prog = prog.fn, prog
        shape = prog.args[0].shape
        self.ring = make_ring(traffic["values"], shape, prog.sharding,
                              traffic["ring"], seed)
        self.state = jax.jit(lambda: jnp.zeros(shape, jnp.float32),
                             out_shardings=prog.sharding)()
        self._last_in = None

    def hlo_text(self) -> str:
        # the timed function itself, so that the instruction names match
        # the executable the trace records
        return self._prog.fn.lower(self.ring[0], self.state).compile(
        ).as_text()
