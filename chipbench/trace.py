"""Reduce a profiler trace (``.xplane.pb``) of the measured window.

Device planes are ``/device:TPU:<i>``; each holds a line ``XLA Ops`` whose
events are the operations the chip ran, with a start and a duration in
nanoseconds on the same clock as the host's.  An event's name is the whole
HLO instruction (``%fusion.12 = bf16[...] fusion(...)``); an op is named
by the instruction's name, and a Pallas kernel is an op that is a
``tpu_custom_call`` (named after the jitted wrapper that holds the
``pallas_call``, e.g. ``vmap_jvp_jit_rmsnorm___.8``).  The window is the
host span ``chipbench.window`` (a ``TraceAnnotation`` of the harness).
From these:

- ``busy_s``: the union of the device's op intervals inside the window,
  averaged over the chips used; ``window_s``: the window's length;
- ``ops``: per op name, seconds inside the window (summed over the chips
  used, over their number) and the number of events;
- ``kernels``: the same, for the Pallas kernels alone;
- ``top_ops``: the ten ops that took most time;
- ``idle_gaps``: the longest intervals in which chip 0 ran nothing, each
  named by the innermost harness span (``chipbench.<name>``) open at its
  middle, or ``outside any span``.
"""
from __future__ import annotations

import glob
import re
from pathlib import Path
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "chipbench."


def profile_options():
    """The profiler's options for a traced run: host annotations on, the
    Python function tracer off (it records every Python call of the host
    path and slows it several-fold)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def find_xspace(trace_dir: Path) -> Path:
    hits = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return Path(hits[-1])


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def op_name(hlo: str) -> Tuple[str, bool]:
    """(instruction name, whether it is a Pallas kernel) of an event."""
    name = hlo.split(" = ", 1)[0].strip().lstrip("%")
    return name, "custom-call(" in hlo and "tpu_custom_call" in hlo


def reduce_events(device_ops: Dict[int, List[Tuple[str, float, float]]],
                  host_spans: List[Tuple[str, float, float]],
                  window: Tuple[float, float], chips: int) -> dict:
    """The reduction itself, on plain tuples (ns): ``device_ops[chip]`` is a
    list of (HLO instruction, start, end); ``host_spans`` (name, start,
    end)."""
    w0, w1 = window
    used = sorted(device_ops)[:chips]
    if not used:
        raise ValueError("the trace holds no device plane")
    busy_total = 0.0
    ops: Dict[str, List[float]] = {}
    kernels: Dict[str, List[float]] = {}
    gaps0: List[Tuple[float, float]] = []
    for i, chip in enumerate(used):
        clipped = []
        for hlo, a, b in device_ops[chip]:
            a, b = max(a, w0), min(b, w1)
            if b <= a:
                continue
            clipped.append((a, b))
            name, is_kernel = op_name(hlo)
            for table in (ops, kernels) if is_kernel else (ops,):
                rec = table.setdefault(name, [0.0, 0])
                rec[0] += (b - a) / len(used)
                rec[1] += 1
        merged = _union(clipped)
        busy_total += sum(b - a for a, b in merged)
        if i == 0:
            t = w0
            for a, b in merged:
                if a > t:
                    gaps0.append((t, a))
                t = max(t, b)
            if w1 > t:
                gaps0.append((t, w1))
    spans = [(n[len(SPAN_PREFIX):], a, b) for n, a, b in host_spans
             if n.startswith(SPAN_PREFIX) and n != SPAN_PREFIX + "window"]

    def label(a: float, b: float) -> str:
        mid = 0.5 * (a + b)
        open_ = [(e - s, n) for n, s, e in spans if s <= mid <= e]
        return min(open_)[1] if open_ else "outside any span"

    gaps = sorted(((label(a, b), (b - a) * 1e-9) for a, b in gaps0),
                  key=lambda g: -g[1])
    top = sorted(((n, v[0] * 1e-9) for n, v in ops.items()),
                 key=lambda o: -o[1])
    return {"busy_s": busy_total / len(used) * 1e-9,
            "window_s": (w1 - w0) * 1e-9,
            "ops": {n: [v[0] * 1e-9, v[1]] for n, v in ops.items()},
            "kernels": {n: [v[0] * 1e-9, v[1]] for n, v in kernels.items()},
            "top_ops": [list(t) for t in top[:10]],
            "idle_gaps": [list(g) for g in gaps[:10]]}


def read_xspace(path: Path):
    """(device ops per chip, host spans) from an ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    device: Dict[int, List[Tuple[str, float, float]]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = device.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((e.name, e.start_ns, e.end_ns)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return device, host


def reduce(path: Path, chips: int,
           window_span: str = SPAN_PREFIX + "window") -> dict:
    device, host = read_xspace(path)
    wins = [(a, b) for n, a, b in host if n == window_span]
    if not wins:
        raise ValueError(f"no host span {window_span!r} in the trace")
    out = reduce_events(device, host, wins[-1], chips)
    out["planes"] = sorted(device)
    return out
