"""Bytes one chip sends into collectives a window step: the operand bytes
of every collective its op line starts in the window
(``chipbench/collectives.py:started_bytes``, from the types the events'
HLO prints: an asynchronous collective once, at its start; a layer loop's
once a layer), summed over the chips used, over their number, over the
window's steps.  It changes only when the compiled step's communication
does.  Where the window holds op events but none starts a collective, the
stderr line says so and nothing is returned."""
import sys
from collections import Counter

from chipbench import collectives as C
from chipbench import trace as T


def started(device_ops, window, chips: int):
    """(bytes started, averaged over the chips used; bytes per op kind,
    likewise) inside ``window``."""
    used = min(chips, len(device_ops)) or 1
    kinds = Counter()
    for _, hlo, _, _ in C.in_window(device_ops, window, chips):
        n = C.started_bytes(hlo)
        if n:
            kinds[T.op_name(hlo)[0].split(".")[0]] += n / used
    return sum(kinds.values()), kinds


def read(ctx):
    steps = len(ctx.window["steps"])
    ops = C.window_ops(ctx) if steps else None
    if ops is None:
        return None
    nbytes, kinds = started(*ops, ctx.chips)
    top = ", ".join(f"{k} {v / steps!r}" for k, v in kinds.most_common())
    print(f"[step_collective_bytes] a chip a step: {nbytes / steps!r} bytes "
          f"({top or 'no op starts a collective'})", file=sys.stderr)
    return nbytes / steps if kinds else None
