"""Roofline-term extraction from a lowered/compiled cell.

compute term    = HLO_FLOPs / (chips * peak)
memory term     = HLO_bytes / (chips * hbm_bw)
collective term = collective_bytes / (chips * link_bw)

HLO_FLOPs / bytes come from compiled.cost_analysis().  Collective bytes are
NOT in cost_analysis: we parse the compiled HLO text and sum *operand* sizes
of all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute,
deriving operand size from the printed result shape and replica-group size
(all-gather result = operand x G; reduce-scatter result = operand / G).
``collective_bytes`` counts each instruction of the text once;
``executed_collective_bytes`` counts what one execution starts, a loop
body's collectives once a trip, from the operands' own types.
"""
from __future__ import annotations

import math
import re
from typing import Dict, Optional

DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_INSTR = re.compile(
    r"=\s+(?:\()?([a-z0-9]+)\[([\d,]*)\][^\s]*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
)
_TUPLE_INSTR = re.compile(
    r"=\s+\(((?:[a-z0-9]+\[[\d,]*\][^,)]*(?:,\s*)?)+)\)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
)
_GROUPS_LIST = re.compile(r"replica_groups=\{(.*?)\}\}?", re.S)
_GROUPS_IOTA = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_SHAPE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * DTYPE_BYTES.get(dtype, 4)


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST.search(line)
    if m:
        first = m.group(1).split("}")[0].strip("{} ")
        if first:
            return len([x for x in first.split(",") if x.strip() != ""])
    return 1


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum operand bytes per collective kind over the whole module."""
    out: Dict[str, float] = {k: 0.0 for k in COLLECTIVES}
    seen_done = set()
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue    # async pair: count only the -start
        m = _INSTR.search(line)
        shapes = []
        kind = None
        if m:
            shapes = [(m.group(1), m.group(2))]
            kind = m.group(3)
        else:
            mt = _TUPLE_INSTR.search(line)
            if mt:
                kind = mt.group(2)
                shapes = _SHAPE.findall(mt.group(1))
        if not kind:
            continue
        g = _group_size(line)
        result = sum(_shape_bytes(dt, dims) for dt, dims in shapes)
        if kind == "all-gather":
            operand = result / max(g, 1)
        elif kind == "reduce-scatter":
            operand = result * max(g, 1)
        else:
            operand = result
        out[kind] += operand
    out["total"] = sum(out[k] for k in COLLECTIVES)
    return out


_KINDS = "|".join(COLLECTIVES)
_STARTS_OP = re.compile(r"^(" + _KINDS + r")(?:-start)?$")
_STARTS_NAME = re.compile(r"^(" + _KINDS + r"|async-collective-start)")
_CALLS_KIND = re.compile(r"calls=%(" + _KINDS + r")")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%(\S+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%(\S+)\s+=\s+(.*)$")
_OPCODE = re.compile(r"([\w-]+)\(")
_KNOWN_TRIPS = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_PASS_THROUGH = ("copy", "bitcast", "copy-done", "copy-start")


def _closing(s: str, i: int) -> int:
    """Index of the bracket that closes ``s[i]``."""
    depth = 0
    for j in range(i, len(s)):
        if s[j] in "([{":
            depth += 1
        elif s[j] in ")]}":
            depth -= 1
            if depth == 0:
                return j
    raise ValueError(f"unbalanced HLO: {s[i:i + 80]!r}")


def _split_top(s: str) -> list:
    """``s`` split at the commas outside any bracket."""
    out, depth, start = [], 0, 0
    for j, c in enumerate(s):
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
        elif c == "," and depth == 0:
            out.append(s[start:j].strip())
            start = j + 1
    if s[start:].strip():
        out.append(s[start:].strip())
    return out


def parse_instruction(rhs: str):
    """(result type, opcode, operands, attributes) of the text right of an
    instruction's ``=``.  An operand is ``%name`` in a compiled module's
    text and ``<type> %name`` in a profiler event's."""
    if rhs.startswith("("):
        end = _closing(rhs, 0)
        typ, rest = rhs[:end + 1], rhs[end + 1:].lstrip()
    else:
        typ, _, rest = rhs.partition(" ")
    m = _OPCODE.match(rest)
    if not m:
        return typ, None, [], rest
    end = _closing(rest, m.end() - 1)
    return typ, m.group(1), _split_top(rest[m.end():end]), rest[end + 1:]


def type_bytes(typ: str) -> int:
    """Bytes of an HLO type, a tuple's elements summed."""
    return sum(_shape_bytes(dt, dims) for dt, dims in _SHAPE.findall(typ))


def started_collective(name: str, opcode: Optional[str],
                       attrs: str) -> Optional[str]:
    """The kind of collective an instruction starts, or None: a collective
    op or its ``-start`` (never its ``-done``, which waits for it), or a
    fusion that is one (the TPU's ``async-collective-start``, a fusion named
    for a kind, or one that calls a computation named for a kind, such as
    ``calls=%all-reduce-scatter``).  A fusion that carries an asynchronous
    collective along beside compute (``async_collective_fusion``) or ends
    it (``async-collective-done``) starts nothing."""
    if opcode is None or "-done" in name:
        return None
    m = _STARTS_OP.match(opcode)
    if m:
        return m.group(1)
    if opcode != "fusion":
        return None
    m = _STARTS_NAME.match(name) or _CALLS_KIND.search(attrs)
    return m and ("async-collective" if m.group(1).startswith("async")
                  else m.group(1))


def executed_collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Operand bytes of the collectives one execution of a compiled
    (per-chip) module starts, per kind and ``total``: each starting
    instruction (:func:`started_collective`) counted as often as it runs, a
    ``while`` body's once a trip.  A loop's trips come from its
    ``known_trip_count`` or, where the compiler leaves that out (the TPU),
    from its condition ``i < N`` over an induction variable that starts at
    a constant and steps by 1; a loop counted neither way, or a
    ``conditional``, raises ``ValueError``."""
    comps: Dict[str, list] = {}
    roots: Dict[str, str] = {}
    table: Dict[str, tuple] = {}
    entry, comp = None, None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            comps[comp] = []
            if line.startswith("ENTRY"):
                entry = comp
            continue
        m = _INSTRUCTION.match(line)
        if m and comp is not None:
            name = m.group(2)
            table[name] = parse_instruction(m.group(3))
            comps[comp].append(name)
            if m.group(1):
                roots[comp] = name
    if entry is None:
        raise ValueError("no ENTRY computation in the module's text")

    def ref(operand: str) -> str:
        return operand.rsplit("%", 1)[-1]

    def chase(operand: str) -> tuple:
        name = ref(operand)
        typ, op, args, attrs = table[name]
        while op in _PASS_THROUGH:
            name = ref(args[0])
            typ, op, args, attrs = table[name]
        return name, op, args, attrs

    def constant(name: str) -> Optional[int]:
        _, op, args, _ = chase(name)
        return int(args[0]) if op == "constant" and args[0].isdigit() \
            else None

    def tuple_index(name: str) -> Optional[int]:
        _, op, _, attrs = chase(name)
        m = re.search(r"index=(\d+)", attrs)
        return int(m.group(1)) if op == "get-tuple-element" and m else None

    def trips(name: str, args: list, attrs: str) -> int:
        m = _KNOWN_TRIPS.search(attrs)
        if m:
            return int(m.group(1))
        cond = re.search(r"condition=%([^\s,]+)", attrs).group(1)
        body = re.search(r"body=%([^\s,]+)", attrs).group(1)
        _, op, cmp, cattrs = table[roots[cond]]
        k = tuple_index(cmp[0]) if op == "compare" else None
        n = constant(cmp[1]) if k is not None else None
        _, top, elems, _ = chase(args[0])
        start = constant(elems[k]) if top == "tuple" and n is not None \
            else None
        _, bop, belems, _ = chase(roots[body])
        step = None
        if start is not None and "direction=LT" in cattrs and bop == "tuple":
            _, aop, add, _ = chase(belems[k])
            if aop == "add" and tuple_index(add[0]) == k:
                step = constant(add[1])
        if step != 1:
            raise ValueError(f"cannot count the trips of {name}")
        return n - start

    out: Dict[str, float] = {}

    def walk(comp: str, times: int) -> None:
        for name in comps[comp]:
            typ, op, args, attrs = table[name]
            kind = started_collective(name, op, attrs)
            if kind:
                out[kind] = out.get(kind, 0.0) + times * sum(
                    type_bytes(table[ref(a)][0]) for a in args)
            elif op == "while":
                body = re.search(r"body=%([^\s,]+)", attrs).group(1)
                walk(body, times * trips(name, args, attrs))
            elif op == "call":
                walk(re.search(r"to_apply=%([^\s,]+)", attrs).group(1), times)
            elif op == "conditional":
                raise ValueError(f"cannot count the branches of {name}")

    walk(entry, 1)
    out["total"] = sum(out.values())
    return out


def roofline_terms(flops: float, bytes_accessed: float, coll_bytes: float,
                   chips: int, peak_flops: float = 197e12,
                   hbm_bw: float = 819e9, link_bw: float = 50e9,
                   ) -> Dict[str, float]:
    compute = flops / (chips * peak_flops)
    memory = bytes_accessed / (chips * hbm_bw)
    collective = coll_bytes / (chips * link_bw)
    dom = max(("compute", compute), ("memory", memory),
              ("collective", collective), key=lambda kv: kv[1])
    return {
        "compute_s": compute, "memory_s": memory, "collective_s": collective,
        "bottleneck": dom[0],
        "roofline_s": max(compute, memory, collective),
    }
