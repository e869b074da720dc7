"""Each device plane's clock, put on the host's.

The profiler stamps device events with the device's clock, which on a
four-chip v5e host runs up to ~0.6 ms off the host's.  The host spans
of the calls bound where each module can lie: no module starts before
the host span that issued it begins (``bench.dispatch``), and none ends
after the call's ``bench.wait`` ends (the wait cannot return before the
device is done).  Over the calls this leaves an interval of offsets
that keep every module inside its call:

    max_k(issue_k.start - module_k.start) <= offset
                                          <= min_k(wait_k.end - module_k.end)

The offset taken is the lower end: the device is moved only as far as
the ordering forces.  The interval's width says how well the calls pin
the clock; the true offset lies within it.  A host-clock time is a
device time plus the offset.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Sequence

from bench import trace as tr


class Offset(NamedTuple):
    #: add to a device time to put it on the host's clock (ns)
    offset: float
    #: width of the interval of offsets the calls allow (ns)
    width: float


def offset(modules: Sequence[tr.Event], issues: Sequence[tr.Event],
           waits: Sequence[tr.Event]) -> Optional[Offset]:
    """The offset of one device's clock from the ``k``-th module, issue
    span and wait span of each call, or ``None`` where the counts
    differ or the spans leave no offset that fits every call."""
    if not modules or not (len(modules) == len(issues) == len(waits)):
        return None
    lo = max(i.start - m.start for m, i in zip(modules, issues))
    hi = min(w.end - m.end for m, w in zip(modules, waits))
    if hi < lo:
        return None
    return Offset(lo, hi - lo)


def offsets(trace: tr.Trace) -> Dict[str, Offset]:
    """Each device plane's :class:`Offset` from the trace's call spans
    (``bench.dispatch`` issues, ``bench.wait`` waits); planes that
    cannot be aligned are left out."""
    issues = _named(trace, tr.DISPATCH)
    waits = _named(trace, tr.WAIT)
    out = {}
    for dev, mods in trace.modules.items():
        off = offset(mods, issues, waits)
        if off is not None:
            out[dev] = off
    return out


def _named(trace: tr.Trace, name: str) -> List[tr.Event]:
    return sorted((s for s in trace.spans if s.name == name),
                  key=lambda s: s.start)


class Call(NamedTuple):
    """One read call on the host's clock (ns): the issue span's start,
    the first device's module start and the last device's module end,
    and the wait span's end."""
    issue: float
    device_start: float
    device_end: float
    returned: float


def calls(trace: tr.Trace) -> List[Call]:
    """The read calls (all but the first, as :meth:`Trace.window`) with
    every device aligned; empty where a device plane cannot be."""
    offs = offsets(trace)
    if not offs or len(offs) != len(trace.modules):
        return []
    issues = _named(trace, tr.DISPATCH)
    waits = _named(trace, tr.WAIT)
    return [Call(issues[k].start,
                 min(trace.modules[d][k].start + o.offset
                     for d, o in offs.items()),
                 max(trace.modules[d][k].end + o.offset
                     for d, o in offs.items()),
                 waits[k].end)
            for k in range(1, len(waits))]
