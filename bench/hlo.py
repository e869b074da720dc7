"""Counts read from compiled (post-SPMD) HLO text.

``collective_stats`` and its helpers are a copy of the program's
``repro.launch.hlo_analysis`` parser, kept here so that the yardstick
does not move when the program does.  Each collective op contributes
its result-shape bytes, and ops inside ``while`` bodies are multiplied
by the loop's trip count.  One repair to the copy: the TPU compiler
writes an asynchronous ``collective-permute-start`` with a tuple shape
(``(f32[..], f32[..], u32[], u32[])``), which the original's shape
pattern (no spaces) never matched, so it counted no permutes at all on
the chip's HLO; here a tuple shape matches too, and counts the bytes of
its first element.  ``fusion_count`` and ``permute_done_to_start`` are
the benchmark's own additions.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLL_RE = re.compile(
    r"=\s*(\([^=]*?\)|\S+?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)


def _shape_bytes(shape_str: str) -> int:
    """bytes of 'f32[4,8]' or tuple '(f32[4], bf16[2,2])'."""
    total = 0
    for m in re.finditer(r"([a-z0-9_]+)\[([0-9,]*)\]", shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class CollectiveStats:
    bytes_by_kind: Dict[str, int] = field(default_factory=dict)
    ops_by_kind: Dict[str, int] = field(default_factory=dict)


def _split_computations(text: str) -> Dict[str, List[str]]:
    comps: Dict[str, List[str]] = {}
    cur = None
    for line in text.splitlines():
        m = re.match(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\([^)]*\))?.*\{\s*$",
                     line)
        if m and ("(" in line and ")" in line):
            cur = m.group(1)
            comps[cur] = []
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is not None:
            comps[cur].append(line)
    return comps


def _find_entry(text: str) -> str:
    m = re.search(r"^ENTRY\s+%?([\w.\-]+)", text, re.M)
    return m.group(1) if m else ""


def _constants(lines: List[str]) -> Dict[str, int]:
    out = {}
    for line in lines:
        m = re.match(r"\s*%?([\w.\-]+)\s*=\s*[su]\d+\[\]\s*constant\((\d+)\)",
                     line)
        if m:
            out[m.group(1)] = int(m.group(2))
    return out


_TRIP_RE = re.compile(r'"known_trip_count"\s*:\s*\{\s*"n"\s*:\s*"(\d+)"')


def _trip_from_line(while_line: str) -> int:
    """XLA annotates static loops:
    ``backend_config={"known_trip_count":{"n":N}}``."""
    m = _TRIP_RE.search(while_line)
    return int(m.group(1)) if m else 0


def _trip_count(cond_lines: List[str], all_consts: Dict[str, int]) -> int:
    consts = dict(all_consts)
    consts.update(_constants(cond_lines))
    for line in cond_lines:
        m = re.search(
            r"compare\(\s*%?([\w.\-]+),\s*%?([\w.\-]+)\s*\),\s*direction=LT",
            line)
        if m:
            for name in (m.group(2), m.group(1)):
                if name in consts:
                    return consts[name]
    return 1


def collective_stats(hlo_text: str) -> CollectiveStats:
    """Per-kind collective op counts and result bytes, loop-weighted."""
    comps = _split_computations(hlo_text)
    entry = _find_entry(hlo_text)
    global_consts: Dict[str, int] = {}
    for lines in comps.values():
        global_consts.update(_constants(lines))

    own: Dict[str, List[Tuple[str, int]]] = {}
    calls: Dict[str, List[Tuple[str, int]]] = {}  # (callee, multiplier)
    for name, lines in comps.items():
        own[name] = []
        calls[name] = []
        for line in lines:
            cm = _COLL_RE.search(line)
            if cm:
                shape = cm.group(1)
                if shape.startswith("("):
                    first = re.search(r"[a-z0-9_]+\[[0-9,]*\]", shape)
                    shape = first.group(0) if first else ""
                own[name].append((cm.group(2), _shape_bytes(shape)))
            wm = re.search(
                r"while\(.*?\).*?condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)",
                line)
            if wm:
                cond, body = wm.group(1), wm.group(2)
                trips = _trip_from_line(line) or _trip_count(
                    comps.get(cond, []), global_consts)
                calls[name].append((body, trips))
                continue
            for cs in re.finditer(
                r"(?:to_apply|body|branch_computations)=\{?%?([\w.\-]+)", line
            ):
                callee = cs.group(1)
                if callee in comps and callee != name:
                    calls[name].append((callee, 1))
            fm = re.search(r"fusion\(.*?\).*?calls=%?([\w.\-]+)", line)
            if fm:
                calls[name].append((fm.group(1), 1))

    stats = CollectiveStats(defaultdict(int), defaultdict(int))

    def visit(comp: str, mult: int, depth=0):
        if depth > 50 or comp not in own:
            return
        for kind, b in own[comp]:
            stats.bytes_by_kind[kind] += b * mult
            stats.ops_by_kind[kind] += mult
        for callee, m in calls.get(comp, []):
            visit(callee, mult * m, depth + 1)

    if entry:
        visit(entry, 1)
    else:
        for comp in comps:
            visit(comp, 1)
    stats.bytes_by_kind = dict(stats.bytes_by_kind)
    stats.ops_by_kind = dict(stats.ops_by_kind)
    return stats


def fusion_count(hlo_text: str) -> int:
    """``fusion`` instructions anywhere in the module."""
    return len(re.findall(r"\sfusion\(", hlo_text))


_DONE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?\bcollective-permute-done\(\s*"
    r"(?:\S+\s+)?%?([\w.\-]+)\s*\)")


def permute_done_to_start(hlo_text: str) -> Dict[str, str]:
    """``{done op name: start op name}`` of every asynchronous
    collective-permute: the ``-done`` op's operand is its ``-start``."""
    out = {}
    for line in hlo_text.splitlines():
        m = _DONE_RE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out
