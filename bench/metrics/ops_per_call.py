"""Device op events per call in the traced window (round loop), mean
over the cell's devices."""


def read(r):
    n = r.per_device(lambda ops, lo, hi: sum(
        1 for e in ops if e.start >= lo and e.end <= hi))
    return n / r.calls if n else None
