"""Reduction op registry shared by every data-plane layer.

The kernels' drain identity, the jnp oracle's combine, and the
collectives' identity slot must agree bit-for-bit (the reduce family
re-ships drained slots in capped rounds, so the identity must be
absorbing under the combine).  This module is the single source: all of
:mod:`repro.kernels.block_pack`, :mod:`repro.kernels.ref` and
:mod:`repro.core.comm` resolve ops here, and every entry point
validates the op name instead of silently defaulting.
"""

from __future__ import annotations

import numpy as np

OPS = ("sum", "+", "max")


def _validate(op: str) -> None:
    if op not in OPS:
        raise ValueError(f"unsupported reduction op {op!r} (use 'sum' or 'max')")


def op_combine(op: str):
    """The binary combine of ``op`` as a jnp-traceable callable."""
    import jax.numpy as jnp

    _validate(op)
    return jnp.add if op in ("sum", "+") else jnp.maximum


def op_identity(op: str, dtype) -> np.ndarray:
    """Scalar identity of ``op`` in ``dtype`` (drained slots hold it):
    0 for sum; -inf / the integer minimum for max."""
    import jax.numpy as jnp

    _validate(op)
    dt = np.dtype(dtype)
    if op in ("sum", "+"):
        return np.zeros((), dt)
    if jnp.issubdtype(dt, jnp.inexact):
        return np.asarray(-np.inf, dt)
    return np.asarray(np.iinfo(dt).min, dt)
