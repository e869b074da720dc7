"""Entry: the trainer's ``grad_sync='compressed'`` gradient sync.

The trainer's own shard body, ``repro.optim.compression.
compressed_grad_sync`` on a one-bucket spec, with the library's defaults
(block count from its cost model, ``jnp`` round step).  The error state
is carried from call to call as the trainer carries ``gsync_err``.

The p ranks are stacked on one chip (``layout: "rankstack"``),
``jax.jit(jax.vmap(body, axis_name="x"))``; the exchange lowers to an
on-chip gather.  XLA's baseline is ``psum`` under the same ``vmap``.
"""

from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from bench.data import make_ring


def program(config, traffic, devices):
    """The timed callable, the shapes of its arguments and the job's
    least bytes; ``devices`` may be described ones (AOT compile)."""
    from repro.optim.compression import compressed_grad_sync, make_bucket_spec

    if traffic["layout"] != "rankstack":
        raise ValueError("compressed_grad_sync stacks the ranks on one chip "
                         f"(layout 'rankstack'), not {traffic['layout']!r}")
    p = config["ranks"]
    elems = config["bucket_bytes"] // 4
    spec = make_bucket_spec(jax.ShapeDtypeStruct((elems,), jnp.float32),
                            config["bucket_bytes"])

    def body(g, e):
        mean, errs = compressed_grad_sync(g, [e], "x", p, spec)
        return mean, errs[0]

    sharding = SingleDeviceSharding(devices[0])
    x = jax.ShapeDtypeStruct((p, elems), jnp.float32, sharding=sharding)
    return SimpleNamespace(
        fn=jax.jit(jax.vmap(body, axis_name="x")), args=(x, x),
        sharding=sharding, p=p, payload_bytes=elems * 4,
        # every rank on this chip reads gradient and error, writes mean
        # and new error; nothing crosses chips
        least_hbm_bytes=p * 4 * elems * 4, least_ici_bytes=0.0,
        baseline=jax.jit(jax.vmap(lambda g: jax.lax.psum(g, "x") / p,
                                  axis_name="x")),
        describe=f"compressed_grad_sync p={p} layout=rankstack "
                 f"bytes_per_rank={elems * 4}")


class Job:
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

    def describe(self) -> str:
        return self._prog.describe

    def issue(self, i):
        idx = i % len(self.ring)
        self._last_in = (idx, self.state)
        out = self.fn(self.ring[idx], self.state)
        self.state = out[1]
        return out

    def record(self, i, out):
        idx, e_in = self._last_in
        return (idx, e_in, out)

    def fetch(self, chain):
        """A chain of consecutive calls: each call's gradients, mean and
        new error state (leading axis: the call), and the error state
        the chain's first call was given.  The reference feeds every
        later call the error state that the call before it returned."""
        return {"g": np.stack([np.asarray(self.ring[idx])
                               for idx, _, _ in chain]),
                "e_in": np.asarray(chain[0][1]),
                "mean": np.stack([np.asarray(out[0]) for _, _, out in chain]),
                "err": np.stack([np.asarray(out[1]) for _, _, out in chain])}

    def baseline(self, i):
        return self._prog.baseline(self.ring[i % len(self.ring)])

    def hlo_text(self) -> str:
        return jax.jit(self._prog.fn).lower(
            self.ring[0], self.state).compile().as_text()
