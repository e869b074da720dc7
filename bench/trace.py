"""Profiler capture and the interval arithmetic behind the per-layer metrics.

A traced window is recorded with ``jax.profiler`` (Python tracer off)
while the benchmark wraps each call in host spans named ``bench.call``,
``bench.dispatch`` and ``bench.wait``.  :func:`load` reads the
``.xplane.pb`` the profiler writes into a :class:`Trace`: for every
device plane the op events of its ``XLA Ops`` line, and the benchmark's
host spans.  All times are nanoseconds on the trace's clock, but the
device planes' clocks are only synchronised to the host's to within
about half a millisecond (four-chip v5e host: a module's events start
up to 0.6 ms before the host span of the call that issued it).  So the
per-call reductions take their window from the device's own ``XLA
Modules`` events where each call ran one module, and only the idle share
takes the host's window.

On a TPU an op event is named by its whole HLO instruction
(``%fusion.60 = f32[..] fusion(...), kind=kLoop, ...``); it is read
as the instruction's name (``fusion.60``) and its opcode (``fusion``).
Control-flow ops (``while``, ``conditional``, ``call``) are left out:
their events span the ops of their bodies, which have events of their
own.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

#: Device planes of the chips and the line that holds one event per op.
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: Host spans the benchmark writes around each call.
CALL, DISPATCH, WAIT = "bench.call", "bench.dispatch", "bench.wait"
SPANS = (CALL, DISPATCH, WAIT)
PERMUTE_START = "collective-permute-start"
PERMUTE_DONE = "collective-permute-done"


#: Opcodes whose events contain the events of the ops they run.
CONTAINERS = frozenset({"while", "conditional", "call"})

_INSTR_RE = re.compile(r"^%?([\w.\-]+)\s*=.*?\s([a-z][a-z0-9\-]*)\(")


@dataclass(frozen=True)
class Event:
    name: str
    start: float
    end: float


@dataclass
class Trace:
    """Op events per device plane, and the benchmark's host spans."""

    devices: Dict[str, List[Event]] = field(default_factory=dict)
    spans: List[Event] = field(default_factory=list)
    #: Executions of compiled programs per device plane.
    modules: Dict[str, List[Event]] = field(default_factory=dict)

    def _calls(self) -> List[Event]:
        """The call spans read: all but the first, which pays for the
        profiler's start on the chip (a few ms more than the others)."""
        calls = sorted((s for s in self.spans if s.name == CALL),
                       key=lambda s: s.start)
        return calls[1:]

    def window(self) -> Optional[Interval]:
        """From the start of the first call read to the end of the
        last."""
        calls = self._calls()
        if not calls:
            return None
        return (calls[0].start, max(s.end for s in calls))

    def calls(self) -> int:
        return len(self._calls())

    def device_window(self, device: str) -> Optional[Interval]:
        """The read calls' window on one device's clock: from the start
        of the second module to the end of the last, where the device
        ran one module per call span; else the host's window."""
        mods = self.modules.get(device, [])
        n = sum(1 for s in self.spans if s.name == CALL)
        if n >= 2 and len(mods) == n:
            return (mods[1].start, mods[-1].end)
        return self.window()


# ------------------------------------------------------------ intervals


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge intervals into disjoint, sorted ones."""
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def clip(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in merged if b > lo and a < hi]


def length(merged: Sequence[Interval]) -> float:
    return sum(b - a for a, b in merged)


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of ``[lo, hi]`` that ``merged`` leaves uncovered."""
    out, cur = [], lo
    for a, b in clip(merged, lo, hi):
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        out.append((cur, hi))
    return out


def is_permute(name: str) -> bool:
    return name.startswith(PERMUTE_START) or name.startswith(PERMUTE_DONE)


def permute_intervals(ops: Sequence[Event],
                      done_to_start: Dict[str, str]) -> List[Interval]:
    """In-flight intervals of the asynchronous collective-permutes: from
    each ``-start`` event's begin to its ``-done`` event's end.

    A done is matched to the newest unmatched start of the name that
    ``done_to_start`` (read from the compiled HLO) gives it; a done the
    map does not name takes the oldest unmatched start (the order in
    which one stream issues them)."""
    open_by_name: Dict[str, List[Event]] = {}
    fifo: List[Event] = []
    out = []
    for ev in sorted(ops, key=lambda e: e.start):
        if ev.name.startswith(PERMUTE_START):
            open_by_name.setdefault(ev.name, []).append(ev)
            fifo.append(ev)
        elif ev.name.startswith(PERMUTE_DONE):
            start_name = done_to_start.get(ev.name)
            pending = open_by_name.get(start_name) if start_name else None
            if pending:
                st = pending.pop()
                fifo.remove(st)
            elif fifo:
                st = fifo.pop(0)
                open_by_name[st.name].remove(st)
            else:
                continue
            out.append((st.start, ev.end))
    return out


# --------------------------------------------------------------- loading


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def instruction(text: str) -> Tuple[str, str]:
    """``(name, opcode)`` of an op event's name: a whole HLO
    instruction, or a bare op name."""
    m = _INSTR_RE.match(text)
    if m:
        return m.group(1), m.group(2)
    return text, base_name(text)


def load(path: str) -> Trace:
    """Read a profiler ``.xplane.pb`` into a :class:`Trace`."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    trace = Trace()
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    trace.modules[plane.name] = sorted(
                        (Event(e.name, float(e.start_ns),
                               float(e.start_ns + e.duration_ns))
                         for e in line.events), key=lambda e: e.start)
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    name, opcode = instruction(e.name)
                    if opcode not in CONTAINERS:
                        ops.append(Event(name, float(e.start_ns),
                                         float(e.start_ns + e.duration_ns)))
            trace.devices[plane.name] = sorted(ops, key=lambda e: e.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                trace.spans.extend(
                    Event(e.name, float(e.start_ns),
                          float(e.start_ns + e.duration_ns))
                    for e in line.events if e.name in SPANS)
    trace.spans.sort(key=lambda e: e.start)
    return trace


def base_name(op: str) -> str:
    """``fusion.123`` -> ``fusion``: one name for the copies of an op."""
    return re.sub(r"(\.\d+)+$", "", op)


# ------------------------------------------------------------- capture


def capture(logdir: str, fn):
    """Run ``fn()`` under the profiler, writing into ``logdir``; returns
    ``(fn's result, path of the .xplane.pb)``."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        result = fn()
    finally:
        jax.profiler.stop_trace()
    return result, find_xplane(logdir)
