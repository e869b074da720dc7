"""Plain reference of ``int8_gradsync``: an error-fed lossy mean.

A sample is a chain of ``L`` consecutive calls: ``g`` ``[L, p, m]`` the
ranks' gradients, ``mean`` ``[L, p, m]`` every rank's returned mean,
``err`` ``[L, p, m]`` their new error state (float32, in sum units), and
``e_in`` ``[p, m]`` the error state the chain's first call was given.
Every later call's error state is the one the call before it returned,
as the reference knows it, not as the program passed it on.  A call's
exact target is ``sum_r (g_r + e_r)``, taken in float64.  An int8 step
is one 256-element block's largest exact mean magnitude over 127.
"""

import ml_dtypes
import numpy as np


def _worst(d) -> float:
    d = np.asarray(d, np.float64)
    return float(d.max()) if np.isfinite(d).all() else float("inf")


def _step(mean, qblock):
    amax = np.abs(mean).reshape(-1, qblock).max(1, keepdims=True) / 127.0
    return np.broadcast_to(np.maximum(amax, 1e-30),
                           (mean.size // qblock, qblock)).reshape(-1)


def numbers(sample, config):
    """Over the chain's calls: ``gap_steps``, the widest gap between any
    rank's mean and the exact mean, in int8 steps;
    ``completeness_steps``, the widest gap between ``p * mean + sum of
    the new errors`` (rank 0's mean) and the exact sum, in int8 steps of
    the sum.  Term by term over the chain this is the telescoped law
    ``p * sum_t mean_t + err_last == sum_t sum_r g_t + err_first``."""
    p = sample["g"].shape[1]
    e = sample["e_in"].astype(np.float64)
    gap = comp = 0.0
    for g, got_mean, got_err in zip(sample["g"], sample["mean"],
                                    sample["err"]):
        total = (g.astype(np.float64) + e).sum(0)
        mean = total / p
        step = _step(mean, config["qblock"])
        gap = max(gap, _worst(np.abs(got_mean.astype(np.float64)
                                     - mean[None]) / step[None]))
        recon = (p * got_mean[0].astype(np.float64)
                 + got_err.astype(np.float64).sum(0))
        comp = max(comp, _worst(np.abs(recon - total) / (p * step)))
        e = got_err.astype(np.float64)
    return {"gap_steps": gap, "completeness_steps": comp}


def control(sample, config):
    """The reference in the program's place, one precision below each
    stated one: an int4 wire (scale = amax / 7) for the int8 one, and a
    bfloat16 error state for the float32 one, carried along the chain
    from its first call's error state.  One quantization of the float32
    sum per call; its error belongs to rank 0."""
    qb = config["qblock"]
    p = sample["g"].shape[1]
    e = sample["e_in"].astype(np.float32)
    means, errs = [], []
    for g in sample["g"]:
        total = (g + e).astype(np.float32).sum(0, dtype=np.float32)
        blocks = total.reshape(-1, qb)
        scale = np.maximum(
            np.abs(blocks).max(1, keepdims=True) / np.float32(7),
            np.float32(1e-30))
        deq = (np.clip(np.round(blocks / scale), -7, 7) * scale).reshape(-1)
        e = np.zeros_like(e)
        e[0] = (total - deq).astype(ml_dtypes.bfloat16).astype(np.float32)
        errs.append(e)
        means.append(np.broadcast_to(
            (deq / np.float32(p)).astype(np.float32), g.shape))
    return {**sample, "mean": np.stack(means), "err": np.stack(errs)}
