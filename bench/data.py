"""The one generator of inputs: a ring of distinct payloads made on the
device, in one jitted call, from the run's seed and the ``values`` entry
of a traffic file.

Value kinds:

* ``{"kind": "ints", "low": a, "high": b}``: integer-valued float32 in
  ``[a, b]``.  Sums over a few ranks stay exact in float32, so any
  reduction order gives the same bits.
* ``{"kind": "blockscaled_normal", "block": B, "exp_low": a,
  "exp_high": b}``: standard normal values, each run of ``B`` elements of
  one rank scaled by ``10**k`` with ``k`` drawn from ``[a, b]``: gradient
  buckets whose magnitude differs from block to block and rank to rank.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def key(seed: int, salt: int = 0):
    """A JAX key from a seed of any size (jax.random.key keeps only the
    low 32 bits of a Python int when 64-bit types are off)."""
    words = np.random.SeedSequence([int(seed), int(salt)]).generate_state(1)
    return jax.random.key(int(words[0]))


def _values(k, shape: Tuple[int, ...], spec: Dict):
    kind = spec["kind"]
    if kind == "ints":
        return jax.random.randint(k, shape, spec["low"], spec["high"] + 1
                                  ).astype(jnp.float32)
    if kind == "blockscaled_normal":
        block = spec["block"]
        *lead, m = shape
        if m % block:
            raise ValueError(f"payload of {m} elements is not whole "
                             f"{block}-element blocks")
        k1, k2 = jax.random.split(k)
        x = jax.random.normal(k1, (*lead, m // block, block), jnp.float32)
        e = jax.random.randint(k2, (*lead, m // block, 1), spec["exp_low"],
                               spec["exp_high"] + 1)
        return (x * jnp.float32(10.0) ** e.astype(jnp.float32)).reshape(shape)
    raise ValueError(f"unknown value kind {kind!r}")


def make_ring(values: Dict, shape: Tuple[int, ...], sharding, ring: int,
              seed: int):
    """``ring`` distinct float32 arrays of ``shape``, placed by
    ``sharding``, made in one jitted call."""
    def gen(k):
        return tuple(_values(kk, shape, values)
                     for kk in jax.random.split(k, ring))

    return jax.jit(gen, out_shardings=(sharding,) * ring)(key(seed))
