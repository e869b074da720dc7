"""The program scope of each instruction of a compiled module.

The program names its work with ``jax.named_scope`` (``repro.core.
tracing``): round steps ``roundstep.<method>``, phases and the slot
layout around the round loop ``circulant.<name>``, the gradient
buckets ``gradsync.bucket``.  A scope lands in the ``op_name`` metadata
of each HLO instruction built inside it, fusions included.  An
instruction's scope is the innermost ``roundstep.*`` name in its
``op_name``, else the innermost ``circulant.*`` / ``gradsync.*`` name.

Instructions the compiler adds (copies, in-place update slices) carry
no ``op_name``.  Such an instruction inherits the scope of its first
operand that has one, in program order, so transitively through its
computation; one that still has none inherits the scope of the
control-flow instruction (``while``, ``conditional``, ``call``) that
runs its computation.  Device op events are joined to these scopes by
the instruction name that :func:`bench.trace.instruction` reads.
"""

from __future__ import annotations

import re
from typing import Callable, Dict, List, NamedTuple, Optional

from bench.trace import clip, is_permute, length, union

ROUNDSTEP = "roundstep."
LAYOUT = frozenset({"circulant.split", "circulant.join", "circulant.requant",
                    "gradsync.bucket"})

_SCOPE_RE = re.compile(r"\b(roundstep|circulant|gradsync)\.\w+")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?\s([a-z][a-z0-9\-]*)\(")
_COMP_RE = re.compile(r"^\s*(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_CALLEE_RE = re.compile(
    r"\b(?:condition|body|to_apply|true_computation|false_computation)="
    r"%?([\w.\-]+)|branch_computations=\{([^}]*)\}")
_CONTROL = frozenset({"while", "conditional", "call"})


class Instr(NamedTuple):
    name: str
    opcode: str
    operands: List[str]
    scope: Optional[str]
    callees: List[str]


def scope_of(op_name: str) -> Optional[str]:
    """The program scope an ``op_name`` path names, or ``None``."""
    names = [m.group(0) for m in _SCOPE_RE.finditer(op_name)]
    steps = [s for s in names if s.startswith(ROUNDSTEP)]
    return (steps or names or [None])[-1]


def _operands(line: str, start: int) -> List[str]:
    """Names of the operands in the parenthesis that opens at
    ``start``."""
    depth = 0
    for i in range(start, len(line)):
        if line[i] == "(":
            depth += 1
        elif line[i] == ")":
            depth -= 1
            if depth == 0:
                return re.findall(r"%([\w.\-]+)", line[start:i])
    return re.findall(r"%([\w.\-]+)", line[start:])


def parse(hlo_text: str):
    """``({computation: [Instr]}, entry computation name)``."""
    comps: Dict[str, List[Instr]] = {}
    entry, cur = "", None
    for line in hlo_text.splitlines():
        m = _COMP_RE.match(line)
        if m and "=" not in line.split("{", 1)[0].split("(", 1)[0]:
            cur = m.group(2)
            comps[cur] = []
            if m.group(1):
                entry = cur
            continue
        if line.strip() == "}":
            cur = None
            continue
        im = _INSTR_RE.match(line) if cur is not None else None
        if not im:
            continue
        op = _OP_NAME_RE.search(line)
        callees = []
        if im.group(2) in _CONTROL:
            for cm in _CALLEE_RE.finditer(line):
                callees += ([cm.group(1)] if cm.group(1) else
                            re.findall(r"%?([\w.\-]+)", cm.group(2)))
        comps[cur].append(Instr(im.group(1), im.group(2),
                                _operands(line, im.end() - 1),
                                scope_of(op.group(1)) if op else None,
                                callees))
    return comps, entry


def instruction_scopes(hlo_text: str) -> Dict[str, Optional[str]]:
    """``{instruction name: program scope or None}`` for the entry
    computation and every computation its control flow runs, with the
    inheritance described above."""
    comps, entry = parse(hlo_text)
    out: Dict[str, Optional[str]] = {}

    def resolve(comp: str, outer: Optional[str]) -> None:
        for ins in comps.get(comp, []):
            scope = ins.scope or next(
                (out[o] for o in ins.operands if out.get(o)), outer)
            out[ins.name] = scope
            for callee in ins.callees:
                if callee not in done:
                    done.add(callee)
                    resolve(callee, scope)

    done = {entry}
    resolve(entry, None)
    return out


def has_program_scopes(scopes: Dict[str, Optional[str]]) -> bool:
    """Whether the program named any of its work (a program without the
    tracing names compiles to a module with none)."""
    return any(s is not None for s in scopes.values())


def scoped_ms(r, keep: Callable[[Optional[str]], bool]) -> Optional[float]:
    """Device time per call in which an op other than a collective-
    permute start or done, whose scope ``keep`` accepts, runs: the union
    of those ops' intervals in the traced window, per call, mean over
    the cell's devices.  ``None`` where the compiled module carries no
    program scope, or there is no trace to read."""
    scopes = instruction_scopes(r.hlo)
    if not has_program_scopes(scopes):
        return None
    ns = r.per_device(lambda ops, lo, hi: length(clip(union(
        (e.start, e.end) for e in ops
        if not is_permute(e.name) and keep(scopes.get(e.name))), lo, hi)))
    return None if ns is None else ns / r.calls / 1e6
