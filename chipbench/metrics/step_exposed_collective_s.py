"""Seconds a window step in which a chip's op line is in a collective
(``chipbench/collectives.py:is_collective``: the collective ops, the start
and the ``-done`` wait of an asynchronous one, the fusions that are one):
the communication the step does not hide behind compute.  The union of
those ops' intervals in the window, averaged over the chips used, over the
window's steps.  Where the window holds op events but none is named so,
the stderr line says so and nothing is returned."""
import sys
from collections import Counter

from chipbench import collectives as C
from chipbench import trace as T


def exposed(device_ops, window, chips: int):
    """(seconds in a collective, averaged over the chips used; collective
    op names with their event counts; op events seen) from ``device_ops``
    (chip -> [(HLO, start, end)], ns) inside ``window``."""
    ivs, names, seen = {}, Counter(), 0
    for chip, hlo, a, b in C.in_window(device_ops, window, chips):
        seen += 1
        if C.is_collective(hlo):
            ivs.setdefault(chip, []).append((a, b))
            names[T.op_name(hlo)[0]] += 1
    ns = sum(b - a for chip in ivs for a, b in T._union(ivs[chip]))
    return ns / max(min(chips, len(device_ops)), 1) * 1e-9, names, seen


def read(ctx):
    ops = C.window_ops(ctx) if ctx.window["steps"] else None
    if ops is None:
        return None
    secs, names, seen = exposed(*ops, ctx.chips)
    top = ", ".join(f"{n} x{c}" for n, c in names.most_common(6))
    print(f"[step_exposed_collective_s] {sum(names.values())} collective "
          f"events of {seen} in the window ({top}); {secs!r} s a chip",
          file=sys.stderr)
    if not names:
        print("[step_exposed_collective_s] no op in the window is named as "
              "a collective", file=sys.stderr)
        return None
    return secs / len(ctx.window["steps"])
