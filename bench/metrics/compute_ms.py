"""Device time per call in which an op other than a collective-permute
start or done runs (round step): the union of those ops' intervals in
the traced window, per call, mean over the cell's devices."""

from bench.trace import clip, is_permute, length, union


def busy_ns(ops, lo, hi):
    return length(clip(union((e.start, e.end) for e in ops
                             if not is_permute(e.name)), lo, hi))


def read(r):
    ns = r.per_device(busy_ns)
    return ns / r.calls / 1e6 if ns else None
