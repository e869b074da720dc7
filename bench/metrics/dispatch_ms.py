"""Median host time from issuing a call to its return, before the wait
(plan front end), over the calls of the untraced window."""

import statistics


def read(r):
    if not r.dispatch_s:
        return None
    return statistics.median(r.dispatch_s) * 1e3
