"""Share of the traced window in which no op runs on the device, mean
over the cell's devices."""

from bench.trace import clip, length, union


def read(r):
    if not r.trace or r.trace.window() is None:
        return None
    lo, hi = r.trace.window()
    busy = r.per_device(lambda ops, a, b: length(clip(
        union((e.start, e.end) for e in ops), a, b)), host_window=True)
    if busy is None or hi <= lo:
        return None
    return (1 - busy / (hi - lo)) * 100
