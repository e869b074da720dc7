"""Device time per call of the slot layout around the round loop
(scopes ``circulant.split``, ``circulant.join``, ``circulant.requant``
and ``gradsync.bucket``, ``bench.scopes``): the union of those ops'
intervals in the traced window, per call, mean over the cell's
devices."""

from bench.scopes import LAYOUT, scoped_ms


def read(r):
    return scoped_ms(r, lambda s: s in LAYOUT)
