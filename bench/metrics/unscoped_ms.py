"""Device time per call of the ops other than collective-permutes that
no program scope claims, even by inheritance (``bench.scopes``): what
the compiler inserted on its own.  The union of their intervals in the
traced window, per call, mean over the cell's devices."""

from bench.scopes import scoped_ms


def read(r):
    return scoped_ms(r, lambda s: s is None)
