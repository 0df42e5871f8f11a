"""Where a compiled program's copies go: each ``copy`` of a compiled HLO
module's text labelled by what consumes its result.

A copy the compiler inserts to change a layout carries the ``op_name`` of
the op it copies from, or none, so its scope says where the data comes
from.  What it is for shows in its consumer: the Pallas kernel it feeds,
the scatter of the step's K/V, the dynamic-update-slice that stacks a
layer's cache back into the scan's output, or the loop's carry.  The
consumer is found through the ops that only pass a value on (bitcasts,
tuple plumbing, the two halves of an async copy, further copies):

    {copy name: "<kind>@<scope>"}

``kind``: a custom call's name family (``paged_attention_lut_b4``), or of
a fusion the first of ``dynamic-update-slice``, ``scatter``,
``dynamic-slice`` that it holds, else ``fusion``; ``loop-carry`` where
the value is carried to the loop's next pass, ``output`` where the
program returns it; else the consumer's opcode.  ``scope``: the
consumer's, by :func:`bench.scopes.scope_of` (``""``: none).  Several
consumers are joined by ``+``.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

from bench import trace

_INST = re.compile(r"\s*(ROOT )?%([\w.-]+) = (.+?) ([\w-]+)\((.*)$")
_PATH = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.-]+)")
_BODY = re.compile(r"body=%([\w.-]+)")
# ops that hand their operand on unchanged in what it holds
PASS = ("bitcast", "get-tuple-element", "copy-start", "copy-done", "copy",
        "tuple")
# what a fusion's kind is named for, in this order
FUSED = ("dynamic-update-slice", "scatter", "dynamic-slice")


@dataclasses.dataclass
class Inst:
    comp: str
    opcode: str
    shape: str
    path: str                 # op_name, "" where it has none
    operands: list
    calls: str | None         # a fusion's computation
    body: str | None          # a while's body
    root: bool


def _operands(rest: str) -> list[str]:
    """The ``%names`` inside the operand list, the parentheses that open
    ``rest``'s call."""
    depth, end = 1, len(rest)
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if not depth:
                end = i
                break
    return re.findall(r"%([\w.-]+)", rest[:end])


def parse(text: str) -> dict[str, Inst]:
    """``{instruction name: Inst}`` of a module's text."""
    out, comp = {}, ""
    for line in text.splitlines():
        if line and not line.startswith(" ") and line.rstrip().endswith("{"):
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
            comp = comp.lstrip("%")
            continue
        m = _INST.match(line)
        if not m:
            continue
        path, calls, body = (_PATH.search(line), _CALLS.search(line),
                             _BODY.search(line))
        out[m.group(2)] = Inst(
            comp, m.group(4), m.group(3), path.group(1) if path else "",
            _operands(m.group(5)), calls.group(1) if calls else None,
            body.group(1) if body else None, bool(m.group(1)))
    return out


def copy_consumers(text: str) -> dict[str, str]:
    """Each ``copy`` outside fused computations, by what its result
    feeds (module docstring)."""
    from bench.scopes import scope_of
    insts = parse(text)
    users, bodies, holds = defaultdict(list), set(), defaultdict(set)
    for name, i in insts.items():
        for o in i.operands:
            users[o].append(name)
        if i.body:
            bodies.add(i.body)
        holds[i.comp].add(i.opcode)
    fused = {i.calls for i in insts.values() if i.calls}

    def kind(name: str) -> str:
        i = insts[name]
        if i.opcode == "custom-call":
            return trace.op_family(name)
        if i.opcode == "fusion":
            return next((k for k in FUSED if k in holds[i.calls]),
                        "fusion")
        if i.opcode == "while":
            return "loop-carry"
        return i.opcode

    def ends(name: str, seen: set) -> set[str]:
        if name in seen:
            return set()
        seen.add(name)
        i, out = insts[name], set()
        if i.root and i.opcode in PASS:
            out.add("loop-carry" if i.comp in bodies else "output")
        for u in users[name]:
            ui = insts[u]
            if ui.opcode in PASS:
                out |= ends(u, seen)
            else:
                out.add(f"{kind(u)}@{scope_of(ui.path)}")
        return out

    return {name: "+".join(sorted(ends(name, set())))
            for name, i in insts.items()
            if i.opcode == "copy" and i.comp not in fused}
