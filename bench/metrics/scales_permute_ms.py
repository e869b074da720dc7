"""Time per call in which a permute of the quantized wire's per-block
scales is in flight (exchange): the union of the intervals from each
``-start`` event whose program scope is ``circulant.scales`` (``bench.
scopes``) to its ``-done`` event's end in the traced window, per call,
mean over the cell's devices.  ``None`` where no permute carries that
scope."""

from bench.hlo import permute_done_to_start
from bench.scopes import instruction_scopes
from bench.trace import PERMUTE_START, clip, length, permute_intervals, union

SCALES = "circulant.scales"


def read(r):
    starts = {name for name, scope in instruction_scopes(r.hlo).items()
              if scope == SCALES and name.startswith(PERMUTE_START)}
    if not starts:
        return None
    pairs = permute_done_to_start(r.hlo)
    ns = r.per_device(lambda ops, lo, hi: length(clip(union(permute_intervals(
        [e for e in ops if e.name in starts or pairs.get(e.name) in starts],
        pairs)), lo, hi)))
    return ns / r.calls / 1e6 if ns else None
