"""Time per call in which a collective-permute is in flight (exchange):
the union of the intervals from each ``-start`` event's begin to its
``-done`` event's end in the traced window, per call, mean over the
cell's devices."""

from bench.hlo import permute_done_to_start
from bench.trace import clip, length, permute_intervals, union


def read(r):
    pairs = permute_done_to_start(r.hlo)
    ns = r.per_device(lambda ops, lo, hi: length(clip(
        union(permute_intervals(ops, pairs)), lo, hi)))
    return ns / r.calls / 1e6 if ns else None
