"""Collective-traffic extraction from compiled (post-SPMD) HLO text.

``cost_analysis`` gives per-device FLOPs and memory bytes but no
collective volume, so the roofline's collective term is derived here by
parsing the compiled module:

  * every all-reduce / all-gather / reduce-scatter / all-to-all /
    collective-permute op contributes its *result-shape* bytes
    (per-device wire volume approximation);
  * ops inside `while` bodies (jax.lax.scan over layers / microbatches)
    are multiplied by the loop trip count, recovered from the loop
    condition's `compare(.., constant(N)), direction=LT`;
  * op count x trips is also reported as "rounds" -- the latency metric
    the paper's n-1+ceil(log2 p) bound speaks to.
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

# The TPU compiler writes an asynchronous ``collective-permute-start``
# with a tuple shape (``(f32[..], f32[..], u32[], u32[])``): the shape
# group takes a parenthesised tuple too, and such an op counts the bytes
# of the tuple's first element.
_COLL_RE = re.compile(
    r"=\s*(\([^=]*?\)|\S+?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_FIRST_SHAPE_RE = re.compile(r"[a-z0-9_]+\[[0-9,]*\]")
_SHAPE_RE = re.compile(r"^\(?([a-z0-9]+)\[([0-9,]*)\]")


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

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())

    @property
    def total_rounds(self) -> int:
        return sum(self.ops_by_kind.values())

    def as_dict(self):
        return {
            "collective_bytes": self.total_bytes,
            "collective_rounds": self.total_rounds,
            "bytes_by_kind": dict(self.bytes_by_kind),
            "ops_by_kind": dict(self.ops_by_kind),
        }


def _split_computations(text: str) -> Dict[str, List[str]]:
    comps: Dict[str, List[str]] = {}
    cur = None
    for line in text.splitlines():
        m = re.match(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\([^)]*\))?.*\{\s*$", line)
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
    for l in lines:
        m = re.match(r"\s*%?([\w.\-]+)\s*=\s*[su]\d+\[\]\s*constant\((\d+)\)", l)
        if m:
            out[m.group(1)] = int(m.group(2))
    return out


_TRIP_RE = re.compile(r'"known_trip_count"\s*:\s*\{\s*"n"\s*:\s*"(\d+)"')


def _trip_from_line(while_line: str) -> int:
    """XLA annotates static loops: backend_config={"known_trip_count":{"n":N}}."""
    m = _TRIP_RE.search(while_line)
    return int(m.group(1)) if m else 0


def _trip_count(cond_lines: List[str], all_consts: Dict[str, int]) -> int:
    consts = dict(all_consts)
    consts.update(_constants(cond_lines))
    for l in cond_lines:
        m = re.search(
            r"compare\(\s*%?([\w.\-]+),\s*%?([\w.\-]+)\s*\),\s*direction=LT", l
        )
        if m:
            for name in (m.group(2), m.group(1)):
                if name in consts:
                    return consts[name]
    return 1


_DOT_RE = re.compile(
    r"=\s*([a-z0-9_]+)\[([0-9,]*)\][^=]*?\bdot\(\s*%?([\w.\-]+)\s*,"
)
_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\S+?)\s+[a-z]")
_PARAM_SIG_RE = re.compile(r"%?([\w.\-]+):\s*([a-z0-9_]+\[[0-9,]*\])")


def _numel(dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n


def _comp_shapes(header: str, lines: List[str]) -> Dict[str, str]:
    """name -> shape-string map for one computation (params + op defs)."""
    shapes: Dict[str, str] = {}
    for m in _PARAM_SIG_RE.finditer(header):
        shapes[m.group(1)] = m.group(2)
    for l in lines:
        d = _DEF_RE.match(l)
        if d:
            shapes[d.group(1)] = d.group(2)
    return shapes


def _dot_flops(line: str, shapes: Dict[str, str]) -> int:
    m = _DOT_RE.search(line)
    if not m:
        return 0
    out_elems = _numel(m.group(2))
    lhs = shapes.get(m.group(3), "")
    sm = re.match(r"[a-z0-9_]+\[([0-9,]*)\]", lhs)
    if not sm:
        return 0
    lhs_dims = [int(x) for x in sm.group(1).split(",")] if sm.group(1) else []
    cm = _LHS_CONTRACT_RE.search(line)
    k = 1
    if cm and cm.group(1):
        for idx in cm.group(1).split(","):
            i = int(idx)
            if i < len(lhs_dims):
                k *= lhs_dims[i]
    return 2 * out_elems * k


def weighted_cost(hlo_text: str) -> Dict[str, float]:
    """Loop-corrected per-device costs parsed from compiled HLO text.

    XLA's cost_analysis() counts while bodies ONCE; this walks the call
    graph multiplying by trip counts (layer scans, microbatch scans):
      * flops: dot ops only (elementwise is noise at model scale),
      * bytes: 2x the result bytes of every materializing op (one write
        + amortized one read) -- an HBM-traffic estimate consistent
        across cells.
    """
    comps_raw: Dict[str, Tuple[str, List[str]]] = {}
    cur = None
    for line in hlo_text.splitlines():
        m = re.match(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*(\([^{]*\))?.*\{\s*$", line)
        if m and "(" in line:
            cur = m.group(1)
            comps_raw[cur] = (line, [])
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is not None:
            comps_raw[cur][1].append(line)

    entry = _find_entry(hlo_text)
    global_consts: Dict[str, int] = {}
    for _, lines in comps_raw.values():
        global_consts.update(_constants(lines))

    _MATERIALIZE = re.compile(
        r"=\s*(\S+?)\s+(fusion|dot|custom-call|copy|convolution|scatter|gather|"
        r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
        r"dynamic-update-slice|reduce|sort|select-and-scatter)\("
    )

    own_flops: Dict[str, int] = {}
    own_bytes: Dict[str, int] = {}
    calls: Dict[str, List[Tuple[str, int]]] = {}
    for name, (header, lines) in comps_raw.items():
        shapes = _comp_shapes(header, lines)
        fl = 0
        by = 0
        calls[name] = []
        for l in lines:
            fl += _dot_flops(l, shapes)
            mm = _MATERIALIZE.search(l)
            if mm:
                op = mm.group(2)
                if op in ("dynamic-update-slice", "scatter"):
                    # in-place update: traffic is the update operand, not
                    # the whole buffer (XLA aliases the result)
                    ops_m = re.search(
                        r"(?:dynamic-update-slice|scatter)\(([^)]*)\)", l
                    )
                    upd_bytes = 0
                    if ops_m:
                        names = [
                            o.strip().lstrip("%")
                            for o in ops_m.group(1).split(",")
                        ]
                        idx = 1 if op == "dynamic-update-slice" else 2
                        if len(names) > idx:
                            upd_bytes = _shape_bytes(shapes.get(names[idx], ""))
                    by += 2 * (upd_bytes or _shape_bytes(mm.group(1)) // 16)
                else:
                    by += 2 * _shape_bytes(mm.group(1))
            wm = re.search(
                r"while\(.*?\).*?condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)", l
            )
            if wm:
                trips = _trip_from_line(l) or _trip_count(
                    comps_raw.get(wm.group(1), ("", []))[1], global_consts)
                calls[name].append((wm.group(2), trips))
                continue
            for cs in re.finditer(
                r"(?:to_apply|calls|body|branch_computations)=\{?%?([\w.\-]+)", l
            ):
                if cs.group(1) in comps_raw and cs.group(1) != name:
                    calls[name].append((cs.group(1), 1))
        own_flops[name] = fl
        own_bytes[name] = by

    total = {"flops": 0.0, "bytes": 0.0}

    def visit(comp: str, mult: int, depth=0):
        if depth > 60 or comp not in own_flops:
            return
        total["flops"] += own_flops[comp] * mult
        total["bytes"] += own_bytes[comp] * mult
        for callee, m in calls.get(comp, []):
            visit(callee, mult * m, depth + 1)

    visit(entry if entry else next(iter(comps_raw), ""), 1)
    return {"flops_weighted": total["flops"], "bytes_weighted": total["bytes"]}


def collective_stats(hlo_text: str) -> CollectiveStats:
    comps = _split_computations(hlo_text)
    entry = _find_entry(hlo_text)
    global_consts: Dict[str, int] = {}
    for lines in comps.values():
        global_consts.update(_constants(lines))

    # map: computation -> list of (kind, bytes)
    own: Dict[str, List[Tuple[str, int]]] = {}
    calls: Dict[str, List[Tuple[str, int]]] = {}  # (callee, multiplier)
    for name, lines in comps.items():
        own[name] = []
        calls[name] = []
        for l in lines:
            cm = _COLL_RE.search(l)
            if cm:
                shape = cm.group(1)
                if shape.startswith("("):
                    first = _FIRST_SHAPE_RE.search(shape)
                    shape = first.group(0) if first else ""
                own[name].append((cm.group(2), _shape_bytes(shape)))
            wm = re.search(
                r"while\(.*?\).*?condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)", l
            )
            if wm:
                cond, body = wm.group(1), wm.group(2)
                trips = _trip_from_line(l) or _trip_count(
                    comps.get(cond, []), global_consts)
                calls[name].append((body, trips))
                continue
            for cs in re.finditer(
                r"(?:to_apply|body|branch_computations)=\{?%?([\w.\-]+)", l
            ):
                callee = cs.group(1)
                if callee in comps and callee != name:
                    calls[name].append((callee, 1))
            fm = re.search(r"fusion\(.*?\).*?calls=%?([\w.\-]+)", l)
            if fm:
                calls[name].append((fm.group(1), 1))

    stats = CollectiveStats(defaultdict(int), defaultdict(int))
    seen: Dict[str, None] = {}

    def visit(comp: str, mult: int, depth=0):
        if depth > 50 or comp not in own:
            return
        for kind, b in own[comp]:
            stats.bytes_by_kind[kind] = stats.bytes_by_kind.get(kind, 0) + b * mult
            stats.ops_by_kind[kind] = stats.ops_by_kind.get(kind, 0) + mult
        for callee, m in calls.get(comp, []):
            visit(callee, mult * m, depth + 1)

    if entry:
        visit(entry, 1)
    else:  # fallback: flat count
        for comp in comps:
            visit(comp, 1)
    stats.bytes_by_kind = dict(stats.bytes_by_kind)
    stats.ops_by_kind = dict(stats.ops_by_kind)
    return stats
