"""The program's own spans in a traced run: ``repro.<name>`` host events
(``src/repro/spans.py``), each with its counts as the event's stats, on the
profiler's clock beside the device's operations.

A traced run's ``.xplane.pb`` (found as ``run.py`` finds it) is read once
and the summary kept on the metric readers' context.  Kept: each program
span that lies inside the ``chipbench.window`` span.  On standard error,
one ``[spans]`` line per span name (calls; seconds and counts, summed
over the window and per window step) and one coverage line: the seconds of the window in which chip 0 ran
nothing, and the share of them under a leaf program span (a span with no
program span inside it); both also up to the end of the window's last
step, where ``tokens_per_s`` ends its window (the benchmark's own readings
after it are no program work).  The program's spans come from one host
thread, so one span inside another is its child.

Where the trace holds no program span, as a program from before them
writes none, every reader returns None.
"""
from __future__ import annotations

import sys
from typing import Dict, List, Optional, Tuple

from chipbench import trace as T
from chipbench.run import TRACE_DIR

PREFIX = "repro."
#: stats that name a span (the step's number, the stage) and are not summed
LABELS = ("step", "stage")

Span = Tuple[str, float, float, Dict[str, int]]


def read_trace(path) -> Tuple[Dict[int, List[Tuple[float, float]]],
                              List[Span], Optional[Tuple[float, float]]]:
    """(device op intervals per chip, program spans, the last window span)
    from an ``.xplane.pb``; times in ns."""
    from jax.profiler import ProfileData
    device: Dict[int, List[Tuple[float, float]]] = {}
    spans: List[Span] = []
    window = None
    for plane in ProfileData.from_file(str(path)).planes:
        m = T.DEVICE_PLANE.match(plane.name)
        if m:
            ops = device.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == T.OPS_LINE:
                    ops.extend((e.start_ns, e.end_ns) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        spans.append((e.name, e.start_ns, e.end_ns,
                                      dict(e.stats)))
                    elif e.name == T.SPAN_PREFIX + "window":
                        window = (e.start_ns, e.end_ns)
    return device, spans, window


def _measure(ivs: List[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in ivs)


def _intersect(xs: List[Tuple[float, float]],
               ys: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def leaves(spans: List[Span]) -> List[Span]:
    """The spans with no other span inside them."""
    return [s for s in spans
            if not any(t is not s and s[1] <= t[1] and t[2] <= s[2]
                       for t in spans)]


def coverage(ops: List[Tuple[float, float]], leaf: List[Tuple[float, float]],
             w0: float, w1: float) -> Tuple[float, float]:
    """(seconds of [w0, w1] in which no op ran, seconds of them under
    ``leaf``, a sorted list of disjoint intervals)."""
    busy = T._union([(max(a, w0), min(b, w1)) for a, b in ops
                     if b > w0 and a < w1])
    idle, t = [], w0
    for a, b in busy:
        if a > t:
            idle.append((t, a))
        t = max(t, b)
    if w1 > t:
        idle.append((t, w1))
    return _measure(idle) * 1e-9, _measure(_intersect(idle, leaf)) * 1e-9


def summarize(device: Dict[int, List[Tuple[float, float]]],
              spans: List[Span], window: Tuple[float, float]) -> dict:
    """Per span name, seconds, calls and summed counts over the window's
    steps (``repro.step`` spans); ``idle`` (None without a device plane):
    chip 0's idle seconds and the seconds of them under a leaf span, in
    the window (``window``) and up to the end of its last step
    (``steps``)."""
    w0, w1 = window
    inside = [s for s in spans if w0 <= s[1] and s[2] <= w1]
    step_ends = [b for name, _, b, _ in inside if name == PREFIX + "step"]
    by_name: Dict[str, Dict[str, float]] = {}
    for name, a, b, stats in inside:
        rec = by_name.setdefault(name, {"seconds": 0.0, "calls": 0})
        rec["seconds"] += (b - a) * 1e-9
        rec["calls"] += 1
        for k, v in stats.items():
            if k not in LABELS:
                rec[k] = rec.get(k, 0) + v
    idle = None
    if device:
        ops = device[min(device)]
        leaf = T._union([(a, b) for _, a, b, _ in leaves(inside)])
        idle = {"window": coverage(ops, leaf, w0, w1),
                "steps": coverage(ops, leaf, w0, max(step_ends, default=w1))}
    return {"steps": len(step_ends), "spans": by_name, "idle": idle}


def report(summary: dict) -> List[str]:
    """The ``[spans]`` lines."""
    n = max(summary["steps"], 1)
    out = [f"[spans] {summary['steps']} window steps; per span: calls, "
           f"then each sum in the window (a window step)"]
    for name, rec in sorted(summary["spans"].items()):
        sums = ", ".join(f"{k} {v!r} ({v / n!r})" for k, v in rec.items()
                         if k != "calls")
        out.append(f"[spans] {name}: {rec['calls']} calls, {sums}")
    if summary["idle"] is None:
        out.append("[spans] coverage: the trace holds no device plane")
        return out
    parts = []
    for where, (idle, covered) in (
            ("in the window", summary["idle"]["window"]),
            ("to the end of its last step", summary["idle"]["steps"])):
        share = 100.0 * covered / idle if idle > 0 else 100.0
        parts.append(f"{where} chip 0 idle {idle!r} s, {share!r}% of it "
                     f"under a leaf program span")
    out.append("[spans] coverage: " + "; ".join(parts))
    return out


def load(ctx) -> Optional[dict]:
    """The window's summary, read once a run and kept on ``ctx``; None for
    an untraced run or a trace with no program span in the window."""
    if ctx.trace is None:
        return None
    if not hasattr(ctx, "program_spans"):
        device, spans, window = read_trace(T.find_xspace(TRACE_DIR))
        summary = None
        if window is not None:
            summary = summarize(device, spans, window)
            if summary["spans"]:
                for line in report(summary):
                    print(line, file=sys.stderr, flush=True)
            else:
                summary = None
        if summary is None:
            print("[spans] no program span in the window", file=sys.stderr,
                  flush=True)
        ctx.program_spans = summary
    return ctx.program_spans


def total(ctx, key: str, *names: str) -> Optional[float]:
    """``key`` (``seconds`` or a count) of the spans ``repro.<name>``,
    summed over the window; None where none of them ran."""
    s = load(ctx)
    recs = [s["spans"][PREFIX + n] for n in names
            if s and PREFIX + n in s["spans"]]
    if not recs:
        return None
    return sum(r.get(key, 0) for r in recs)


def per_step(ctx, key: str, *names: str) -> Optional[float]:
    """:func:`total` over the window's steps."""
    v = total(ctx, key, *names)
    steps = load(ctx)["steps"] if v is not None else 0
    return v / steps if steps else None
