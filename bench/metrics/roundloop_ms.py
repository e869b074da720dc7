"""Device time per call of the ops that a phase of the round loop emits
outside its round steps and slot layout (a phase scope such as
``circulant.qreduce`` is the innermost program scope, ``bench.
scopes``): slot-table lookups, and where the ranks are stacked on one
chip the exchange itself, lowered to an on-chip gather.  The union of
their intervals in the traced window, per call, mean over the cell's
devices.  With ``roundstep_ms``, ``layout_ms`` and ``unscoped_ms`` it
parts ``compute_ms`` four ways."""

from bench.scopes import LAYOUT, ROUNDSTEP, scoped_ms


def read(r):
    return scoped_ms(r, lambda s: s is not None and s not in LAYOUT
                     and not s.startswith(ROUNDSTEP))
