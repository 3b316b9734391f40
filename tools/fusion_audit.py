#!/usr/bin/env python3
"""Which matmul fusions recompute their operands?

Reads the text of a program compiled for the TPU (``compiled.as_text()``)
and lists every fusion that holds a ``convolution`` (XLA's name for a matmul
on the MXU), largest first by the compiler's own ``estimated_cycles``, with
what else the fusion holds on each side of the convolution:

- **producers**: the instructions the convolution's operands are computed
  from, inside the fusion.  The convolution runs tile by tile
  (``iteration_bounds``), and a producer is run again for every output tile
  that needs its operand tile: cheap for a cast or a LayerNorm apply, dear
  for an ``erf`` or the threefry rounds of a dropout mask
  (``shift-right-logical``).  XLA's fusion pass clones one producer into
  each of its consumers (``...clone.clone`` in the computation's name).
- **epilogue**: what is applied to the convolution's result, once an
  element (bias, an activation, Adam's update of the weight the gradient is
  for).

``estimated_cycles`` is a count that ranks, not a time: nothing runs here.
Summed over BERT-base's step it read 164.1 ms where the chip took 165.0 and
145.7 where it took 131.1; a single fusion it misjudged by up to 60% (dH
with GELU's derivative: 1.28 ms estimated, 0.78 traced; PERF.md, PR 29).

    python tools/fusion_audit.py step.hlo.txt [--top 30] [--all]

As a module: ``audit(text)`` returns the rows, ``format_rows(rows)`` the
table; ``tests/test_chip_compile.py`` holds BERT's step to it.
"""
from __future__ import annotations

import argparse
import collections
import re
import sys

# opcodes that move no data and do no arithmetic worth a column
_PLUMBING = frozenset({
    "parameter", "constant", "bitcast", "tuple", "get-tuple-element",
    "broadcast", "reshape", "iota", "copy", "transpose", "slice",
    "dynamic-slice", "convert", "bitcast-convert", "concatenate", "pad"})
# an expensive recipe on the vector unit: what must not feed a convolution
RECIPES = {"erf": "erf", "shift-right-logical": "threefry"}

_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_CYCLES = re.compile(r'"estimated_cycles":"(\d+)"')
_BOUNDS = re.compile(r'"iteration_bounds":\[([^\]]*)\]')
_OPNAME = re.compile(r'op_name="([^"]*)"')


Instr = collections.namedtuple(
    "Instr", "name type opcode operands line calls")


def _balanced(s, start):
    """Index just past the parenthesis that closes ``s[start]``."""
    depth = 0
    for i in range(start, len(s)):
        if s[i] == "(":
            depth += 1
        elif s[i] == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(s)


def _parse_instr(line):
    body = line.strip()
    if body.startswith("ROOT "):
        body = body[5:]
    if not body.startswith("%") or " = " not in body:
        return None
    name, rest = body.split(" = ", 1)
    if rest.startswith("("):                    # a tuple type
        end = _balanced(rest, 0)
    else:
        end = rest.find(" ")
    type_, rest = rest[:end], rest[end:].lstrip()
    paren = rest.find("(")
    if paren < 0:
        return None
    opcode = rest[:paren]
    close = _balanced(rest, paren)
    operands = re.findall(r"%([\w.\-]+)", rest[paren:close])
    return Instr(name.lstrip("%"), type_, opcode, operands, body,
                 _CALLS.findall(rest[close:]))


def parse(text):
    """``{computation name: [Instr, ...]}`` of an HLO module's text."""
    comps, current = {}, None
    for line in text.splitlines():
        if current is None:
            m = _HEADER.match(line)
            if m:
                current = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            current = None
        else:
            ins = _parse_instr(line)
            if ins is not None:
                current.append(ins)
    return comps


def _flatten(comps, name, seen=None):
    """Every instruction of computation ``name`` and of what it calls."""
    seen = set() if seen is None else seen
    if name in seen or name not in comps:
        return []
    seen.add(name)
    out = []
    for ins in comps[name]:
        out.append(ins)
        for callee in ins.calls:
            out.extend(_flatten(comps, callee, seen))
    return out


def _sides(comps, name):
    """(producers, epilogue) of the convolutions of one fused computation:
    what their operands are computed from, and everything else.  A nested
    call counts with all it holds, on the side of the instruction that
    makes it."""
    body = comps.get(name, [])
    by_name = {i.name: i for i in body}
    convs = [i for i in body if i.opcode == "convolution"]
    upstream, stack = set(), [o for c in convs for o in c.operands]
    while stack:
        n = stack.pop()
        if n in upstream or n not in by_name:
            continue
        upstream.add(n)
        stack.extend(by_name[n].operands)
    producers, epilogue = [], []
    for ins in body:
        if ins.opcode == "convolution":
            continue
        side = producers if ins.name in upstream else epilogue
        side.append(ins)
        for callee in ins.calls:
            inner = _flatten(comps, callee)
            if any(i.opcode == "convolution" for i in inner):
                p, e = _sides(comps, callee)
                producers.extend(p)
                epilogue.extend(e)
            else:
                side.extend(inner)
    return producers, epilogue


def _histogram(instrs):
    return collections.Counter(i.opcode for i in instrs
                               if i.opcode not in _PLUMBING)


def _recipes(instrs):
    """``{recipe: [type of each value it is computed on, ...]}``."""
    found = {}
    for i in instrs:
        if i.opcode in RECIPES:
            found.setdefault(RECIPES[i.opcode], []).append(i.type)
    return found


def audit(text):
    """One row (a dict) for each fusion that holds a convolution, largest
    ``estimated_cycles`` first."""
    comps = parse(text)
    rows = []
    for instrs in comps.values():
        for ins in instrs:
            if ins.opcode != "fusion" or not ins.calls:
                continue
            inner = _flatten(comps, ins.calls[0])
            convs = [i for i in inner if i.opcode == "convolution"]
            if not convs:
                continue
            producers, epilogue = _sides(comps, ins.calls[0])
            cycles = _CYCLES.search(ins.line)
            bounds = _BOUNDS.search(ins.line)
            op_name = _OPNAME.search(ins.line)
            rows.append({
                "fusion": ins.name,
                "result": ins.type,
                "computation": ins.calls[0],
                "convolution_operands": [
                    next((i.type for i in inner if i.name == o), "?")
                    for o in convs[0].operands],
                "estimated_cycles": int(cycles.group(1)) if cycles else 0,
                "iteration_bounds": bounds.group(1).replace('"', "")
                if bounds else "",
                "op_name": op_name.group(1) if op_name else "",
                "producers": _histogram(producers),
                "epilogue": _histogram(epilogue),
                "producer_recipes": _recipes(producers),
                "epilogue_recipes": _recipes(epilogue),
            })
    rows.sort(key=lambda r: -r["estimated_cycles"])
    return rows


def _short(type_):
    """``f32[32,512,768]{2,1,0:T(8,128)}`` -> ``f32[32,512,768]``."""
    return re.sub(r"\{[^{}]*\}", "", type_)


def _hist_text(hist, most=6):
    items = hist.most_common(most)
    text = " ".join(f"{op}:{n}" for op, n in items)
    return text + (" .." if len(hist) > most else "")


def format_rows(rows, top=None, clock_ghz=1.5):
    total = sum(r["estimated_cycles"] for r in rows)
    dirty = sum(r["estimated_cycles"] for r in rows
                if r["producer_recipes"] or r["epilogue_recipes"])
    lines = [f"{len(rows)} fusions hold a convolution: "
             f"{total / clock_ghz / 1e6:.2f} ms by estimated_cycles at "
             f"{clock_ghz} GHz, {dirty / clock_ghz / 1e6:.2f} ms of it in "
             f"fusions that also hold erf or threefry rounds", ""]
    for r in rows[:top]:
        flags = [f"{k} FEEDS the convolution" for k in r["producer_recipes"]]
        flags += [f"{k} in the epilogue" for k in r["epilogue_recipes"]]
        lines.append(
            f"{r['estimated_cycles']:>10,}  "
            f"{r['estimated_cycles'] / clock_ghz / 1e6:6.3f} ms  "
            f"{r['fusion']}  ->  {_short(r['result'])}")
        lines.append(f"{'':12}operands "
                     + " x ".join(_short(t)
                                  for t in r["convolution_operands"])
                     + f"   bounds [{r['iteration_bounds']}]   "
                     + r["op_name"])
        lines.append(f"{'':12}producers: {_hist_text(r['producers']) or '-'}")
        lines.append(f"{'':12}epilogue:  {_hist_text(r['epilogue']) or '-'}")
        if flags:
            lines.append(f"{'':12}** " + "; ".join(flags))
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("hlo", help="file with compiled.as_text(); - for stdin")
    ap.add_argument("--top", type=int, default=30)
    ap.add_argument("--all", action="store_true", help="every row")
    args = ap.parse_args(argv)
    text = sys.stdin.read() if args.hlo == "-" else open(args.hlo).read()
    print(format_rows(audit(text), None if args.all else args.top))


if __name__ == "__main__":
    main()
