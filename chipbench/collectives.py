"""The collectives among a traced run's device op events, for the layer
``collectives`` (``metrics/step_collective_bytes.py``,
``metrics/step_exposed_collective_s.py``).

An event's name is its whole HLO instruction (``trace.py``), operands
printed with their types: ``%all-reduce.5 = bf16[4,8]{1,0} all-reduce(
bf16[4,8]{1,0} %fusion.3), replica_groups=...``.  From it:

- :func:`is_collective`: the chip is in a collective while the op runs: an
  all-gather, reduce-scatter, all-reduce, all-to-all or collective-permute
  (its opcode), an instruction named for one, the start or the ``-done``
  wait of an asynchronous one (the TPU compiler's ``async-collective-start``
  and ``-done`` included), or a fusion that calls a computation named for
  one (``calls=%all-reduce-scatter``, a reduce-scatter written as an
  all-reduce and a slice).  A fusion that overlaps a collective with
  compute (``calls=%async_collective_fusion.<n>``) is compute.
- :func:`started_bytes`: the operand bytes of a collective the op starts: a
  collective op or its ``-start``, or a fusion that is one; a ``-done``
  starts nothing, so an asynchronous collective counts once.
"""
from __future__ import annotations

import re
from typing import Optional, Tuple

from chipbench import trace as T
from chipbench.run import TRACE_DIR

KINDS = r"(?:all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
OPCODE = re.compile(KINDS + r"(?:-start|-done)?\(")
NAME = re.compile(r"^(?:" + KINDS + r"|async-collective-(?:start|done)\b)")
CALLS = re.compile(r"calls=%" + KINDS)
STARTS_OP = re.compile(r"^" + KINDS + r"(?:-start)?$")
STARTS_NAME = re.compile(r"^(?:" + KINDS + r"|async-collective-start\b)")
SHAPE = re.compile(r"\b([a-z][a-z0-9]*)\[([\d,]*)\]")
DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1,
               "f8e5m2": 1, "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8,
               "u32": 4, "u16": 2, "u8": 1, "pred": 1, "c64": 8, "c128": 16}


def is_collective(hlo: str) -> bool:
    name, _ = T.op_name(hlo)
    text = hlo.split(" = ", 1)[-1]
    return bool(NAME.match(name) or OPCODE.search(text) or CALLS.search(text))


def _closing(text: str, i: int) -> int:
    """Index of the bracket that closes ``text[i]`` (its end if none)."""
    depth = 0
    for j in range(i, len(text)):
        if text[j] in "([{":
            depth += 1
        elif text[j] in ")]}":
            depth -= 1
            if depth == 0:
                return j
    return len(text)


def _opcode(text: str) -> Tuple[Optional[str], str]:
    """(opcode, operands) of the text right of an instruction's ``=``."""
    rest = (text[_closing(text, 0) + 1:].lstrip() if text.startswith("(")
            else text.partition(" ")[2])
    m = re.match(r"([\w-]+)\(", rest)
    if not m:
        return None, ""
    return m.group(1), rest[m.end():_closing(rest, m.end() - 1)]


def started_bytes(hlo: str) -> int:
    """Operand bytes of the collective the op starts; 0 for any other op."""
    name, _ = T.op_name(hlo)
    text = hlo.split(" = ", 1)[-1]
    op, operands = _opcode(text)
    if op is None or "-done" in name:
        return 0
    if not (STARTS_OP.match(op) or op == "fusion" and (
            STARTS_NAME.match(name) or CALLS.search(text))):
        return 0
    return sum(DTYPE_BYTES.get(dt, 4) * _count(dims)
               for dt, dims in SHAPE.findall(operands))


def _count(dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def window_ops(ctx) -> Optional[Tuple[dict, Tuple[float, float]]]:
    """(device ops per chip, the window (ns)) of a traced run, read once a
    run and kept on ``ctx``; None untraced or without a window."""
    if ctx.trace is None:
        return None
    if not hasattr(ctx, "collective_ops"):
        device, host = T.read_xspace(T.find_xspace(TRACE_DIR))
        wins = [(a, b) for n, a, b in host if n == T.SPAN_PREFIX + "window"]
        ctx.collective_ops = (device, wins[-1]) if wins else None
    return ctx.collective_ops


def in_window(device_ops, window, chips: int):
    """(chip, HLO, start, end) of each op event inside ``window``, clipped
    to it, on the first ``chips`` chips."""
    w0, w1 = window
    for chip in sorted(device_ops)[:chips]:
        for hlo, a, b in device_ops[chip]:
            a, b = max(a, w0), min(b, w1)
            if b > a:
                yield chip, hlo, a, b
