"""The least HBM bytes one call needs per chip, at the chip's HBM peak,
as a share of ``compute_ms`` (round step)."""

from bench.metrics import compute_ms


def read(r):
    ms = compute_ms.read(r)
    if not ms or not r.least_hbm_bytes:
        return None
    return r.least_hbm_bytes / r.peaks["hbm_bytes_per_s"] / (ms / 1e3) * 100
