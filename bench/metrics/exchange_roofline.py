"""The least bytes one call must send per chip over the chip-to-chip
interconnect, at its peak, as a share of ``permute_ms`` (exchange)."""

from bench.metrics import permute_ms


def read(r):
    ms = permute_ms.read(r)
    if not ms or not r.least_ici_bytes:
        return None
    return r.least_ici_bytes / r.peaks["ici_bytes_per_s"] / (ms / 1e3) * 100
