"""Device time per call of the ops the program's round steps emit
(scopes ``roundstep.*``, ``bench.scopes``): the union of their
intervals in the traced window, per call, mean over the cell's
devices."""

from bench.scopes import ROUNDSTEP, scoped_ms


def read(r):
    return scoped_ms(r, lambda s: s is not None and s.startswith(ROUNDSTEP))
