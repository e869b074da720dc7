"""Plain reference of ``ddp_allreduce_f32``: every rank ends with the
elementwise sum over the ranks of their inputs.

A sample is one call: ``x`` ``[p, m]`` float32, the ranks' inputs, and
``out`` ``[p, m]``, every rank's output.  The inputs are integer-valued,
so the float64 sum here is the exact float32 sum in any order, and the
comparison is exact.
"""

import ml_dtypes
import numpy as np


def _worst(d) -> float:
    d = np.asarray(d, np.float64)
    return float(d.max()) if np.isfinite(d).all() else float("inf")


def numbers(sample, config):
    """``max_abs_diff``: the largest gap between any rank's output and
    the exact sum, over every element."""
    exact = sample["x"].astype(np.float64).sum(0)
    return {"max_abs_diff": _worst(
        np.abs(sample["out"].astype(np.float64) - exact[None]))}


def control(sample, config):
    """The reference in the program's place, one precision below the
    stated float32: inputs and running sum in bfloat16."""
    xb = sample["x"].astype(ml_dtypes.bfloat16)
    acc = xb[0]
    for row in xb[1:]:
        acc = (acc + row).astype(ml_dtypes.bfloat16)
    out = np.broadcast_to(acc.astype(np.float32), sample["x"].shape)
    return {**sample, "out": out}
