"""Split off-kernel device time by the program stage that emitted it.

The program emits each stage of its step under ``jax.named_scope(<name>)``
(``src/repro/core/scopes.py``); the compiled text carries the name in each
instruction's ``metadata={op_name=".../<name>/..."}``.  An instruction of
the compiled programs is attributed to a stage by these rules, in order:

1. the innermost (last) component of its ``op_name`` that is a stage name;
2. a fusion: its fused computation's root, looking through bitcasts (a
   bitcast moves no data, the instruction under it does the fusion's work);
3. a ``copy`` whose metadata names no stage and whose result is laid out
   in another dimension order than its operand: a transpose the compiler
   put in for a layout (on the loop's state, around a kernel's fixed
   layout), which is ``emit.layout``'s work wherever the compiler put it;
4. an instruction with no metadata (an async copy or a copy the compiler
   inserts): the stage of its first operand that has one, else of its
   first user that has one, passing through other metadata-less ones;
5. else ``unscoped``.

Kernels and collectives are told apart by ``trace.classify_hlo`` and are
left out: the stages split what ``offkernel_busy_frac.iterate`` lumps.
Every function takes plain text and ``{instruction: seconds}``, so it can
be checked on a hand-built program.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

from bench import trace

#: the stage names the program declares.  The benchmark keeps its own copy,
#: so it reads a program that declares none (``stage_shares`` is then empty).
STAGES = ("emit.layout", "emit.accumulate", "kernel.prep", "iterate.repad",
          "halo.exchange", "halo.stitch")
LAYOUT = "emit.layout"
UNSCOPED = "unscoped"

_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%([^\s(]+)\s*\(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([^\s,}]+)")
_OPERAND = re.compile(r"%([^\s,()]+)")
_LAYOUT = re.compile(r"^\w+\[[\d,]*\]\{([\d,]*)")


class Instr(NamedTuple):
    """One instruction of a compiled program: what attribution reads."""
    name: str
    opcode: str
    op_name: Optional[str]
    operands: List[str]
    calls: Optional[str]        # a fusion's fused computation
    comp: str                   # the computation that holds it
    root: bool
    layout: Optional[str]       # minor-to-major order of an array result


def _operands(rest: str, opcode: str) -> List[str]:
    """The ``%names`` between the opcode's parentheses."""
    i = rest.find(opcode + "(")
    if i < 0:
        return []
    i += len(opcode) + 1
    depth, j = 1, i
    while j < len(rest) and depth:
        depth += {"(": 1, ")": -1}.get(rest[j], 0)
        j += 1
    return _OPERAND.findall(rest[i:j - 1])


def parse(texts: Iterable[str]) -> Dict[str, Instr]:
    """Every instruction of the compiled programs, by name."""
    out: Dict[str, Instr] = {}
    for text in texts:
        comp = ""
        for line in text.splitlines():
            c = _COMP.match(line)
            if c and "=" not in line.split("(", 1)[0]:
                comp = c.group(1)
                continue
            m = trace._INSTR.match(line)
            if not m:
                continue
            name, rest = m.groups()
            opcode = trace._first_opcode("= " + rest)
            op_name = _OP_NAME.search(rest)
            calls = _CALLS.search(rest)
            layout = _LAYOUT.match(rest)
            out[name] = Instr(name, opcode, op_name.group(1) if op_name else None,
                              _operands(rest, opcode), calls.group(1) if calls else None,
                              comp, line.lstrip().startswith("ROOT "),
                              layout.group(1) if layout else None)
    return out


def stage_of_op_name(op_name: Optional[str]) -> Optional[str]:
    """The innermost stage named in an ``op_name``, or None.  An op the
    compiler merged carries its sources' names joined by ``;``: the first
    that names a stage counts."""
    for path in (op_name or "").split(";"):
        for part in reversed(path.split("/")):
            if part in STAGES:
                return part
    return None


def attribute(texts: Sequence[str]) -> Dict[str, str]:
    """Instruction name -> stage (or ``unscoped``), by the module's rules."""
    instrs = parse(texts)
    roots = {i.comp: i for i in instrs.values() if i.root}

    def own(i: Instr) -> Optional[str]:
        if i.opcode == "fusion" and i.calls in roots:
            r = roots[i.calls]
            while r.opcode in ("bitcast", "tuple") and r.operands and r.operands[0] in instrs:
                nxt = instrs[r.operands[0]]
                if nxt.opcode == "parameter":
                    break
                r = nxt
            s = stage_of_op_name(r.op_name)
            if s:
                return s
        s = stage_of_op_name(i.op_name)
        if s is None and i.opcode == "copy" and i.operands and i.layout is not None:
            src = instrs.get(i.operands[0])
            if src is not None and src.layout not in (None, i.layout):
                return LAYOUT
        return s

    stage = {n: own(i) for n, i in instrs.items()}
    users: Dict[str, List[str]] = {}
    for n, i in instrs.items():
        for o in i.operands:
            users.setdefault(o, []).append(n)

    def search(n: str, nexts) -> Optional[str]:
        """The first stage along ``nexts``, passing only through
        instructions with no metadata (compiler-inserted), depth first."""
        seen, todo = {n}, list(reversed(nexts(n)))
        while todo:
            m = todo.pop()
            if m in seen or m not in instrs:
                continue
            seen.add(m)
            if stage[m] or instrs[m].op_name is not None:
                if stage[m]:
                    return stage[m]
                continue
            todo.extend(reversed(nexts(m)))
        return None

    out = {}
    for n, i in instrs.items():
        s = stage[n]
        if s is None and i.op_name is None:
            s = (search(n, lambda k: instrs[k].operands)
                 or search(n, lambda k: users.get(k, [])))
        out[n] = s or UNSCOPED
    return out


def op_seconds(device_events: Dict[int, List[trace.Event]],
               window: trace.Interval) -> Dict[str, float]:
    """Device seconds per instruction in ``window``, summed over devices and
    divided by the device count: ``trace.reduce_events``' per-op sum, keyed
    by instruction name alone."""
    lo, hi = window
    n_dev = max(1, len(device_events))
    out: Dict[str, float] = {}
    for events in device_events.values():
        for n, s, e in events:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                out[n] = out.get(n, 0.0) + (e - s) / 1e9 / n_dev
    return out


def stage_seconds(op_s: Dict[str, float], texts: Sequence[str]
                  ) -> Tuple[Dict[str, float], Dict[str, float]]:
    """(off-kernel seconds by stage, unscoped seconds by instruction).

    Kernels and collectives are left out; an instruction the programs do
    not name counts as unscoped.
    """
    kernels, collectives = trace.classify_hlo(texts)
    where = attribute(texts)
    by_stage = {s: 0.0 for s in STAGES + (UNSCOPED,)}
    unscoped: Dict[str, float] = {}
    for n, sec in op_s.items():
        if n in kernels or n in collectives:
            continue
        s = where.get(n, UNSCOPED)
        by_stage[s] += sec
        if s == UNSCOPED:
            unscoped[n] = unscoped.get(n, 0.0) + sec
    return by_stage, unscoped


def stage_shares(op_s: Dict[str, float], busy_s_mean: float,
                 texts: Sequence[str]) -> Dict[str, float]:
    """Each stage's share (%) of device busy time, per chip as
    ``offkernel_busy_frac.iterate`` reads it: together they make up that
    share, up to ops of one class overlapping each other.  Empty where
    nothing was busy or the programs name no stage."""
    named = any(stage_of_op_name(i.op_name) for i in parse(texts).values())
    if busy_s_mean <= 0 or not named:
        return {}
    by_stage, _ = stage_seconds(op_s, texts)
    return {s: 100.0 * sec / busy_s_mean for s, sec in by_stage.items()}
