"""Median over the read calls of the time from the last device's end
of the call's module, on the host's clock (``bench.align``), to the
end of the call's ``bench.wait`` (completion).  The offset is the
least the call order allows, so this is an upper bound on the time the
host takes to see the call done."""

import statistics

from bench import align


def read(r):
    calls = align.calls(r.trace) if r.trace else []
    if not calls:
        return None
    return statistics.median(c.returned - c.device_end for c in calls) / 1e6
