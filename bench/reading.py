"""What a per-layer metric reads: the traced window, the compiled HLO,
the host spans of the untraced window, the job's least bytes and the
chip's peaks.  Each file under ``bench/metrics/`` holds one reader,
``read(reading) -> float | None``; ``None`` means there was nothing to
read, and the harness leaves the metric out of the result line."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

from bench.trace import Event, Trace


@dataclass
class Reading:
    trace: Optional[Trace]
    hlo: str
    #: Host seconds from issuing each call of the untraced window to its
    #: return, before the wait.
    dispatch_s: List[float]
    #: Least bytes one call must move per chip: through HBM, and over
    #: the chip-to-chip interconnect (0 where nothing crosses chips).
    least_hbm_bytes: float
    least_ici_bytes: float
    #: Peaks of the chip, from ``bench/peaks.json``.
    peaks: Dict[str, float] = field(default_factory=dict)

    @property
    def calls(self) -> int:
        return self.trace.calls() if self.trace else 0

    def per_device(self, fn: Callable[[Sequence[Event], float, float],
                                      float],
                   host_window: bool = False) -> Optional[float]:
        """Mean over the traced devices of ``fn(ops, lo, hi)``; ``[lo,
        hi]`` in ns is the read calls' window on each device's own clock
        (:meth:`Trace.device_window`), or with ``host_window`` the host
        spans' window.  ``None`` without a trace, a device plane or a
        traced call."""
        if not self.trace or not self.trace.devices or not self.calls:
            return None
        vals = []
        for dev, ops in self.trace.devices.items():
            lo, hi = (self.trace.window() if host_window
                      else self.trace.device_window(dev))
            vals.append(fn(ops, lo, hi))
        return sum(vals) / len(vals)
