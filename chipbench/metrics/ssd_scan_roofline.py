"""Roofline share of the Mamba2 SSD scan kernel (``kernels/ssd_scan.py``):
the least time its operations and bytes need at the chip's peaks, over the
device time of its events in the window.

The elastic step runs one vmap-batched grad call, whose forward calls the
kernel once per layer over every row of the step (the backward
differentiates the jnp oracle, not the kernel); vmap over items puts them
all in one event (``tests/test_trace.py`` on a recorded trace).  So the
window must hold one event per layer and step, each over a step's tokens;
where it holds another count the work per event is not known and nothing
is returned.  It is memory-bound at the v5e's peaks; the stderr line says
which bound holds."""
import re
import sys

from chipbench import flops

NAME = re.compile(r"ssd_scan", re.I)


def read(ctx):
    t, w = ctx.trace, ctx.window
    if t is None or not w["steps"]:
        return None
    hits = [(s, n) for name, (s, n) in t["kernels"].items()
            if NAME.search(name)]
    secs = sum(s for s, _ in hits)
    events = sum(n for _, n in hits) / ctx.chips
    expected = ctx.spec["n_layer"] * len(w["steps"])
    print(f"[ssd_scan_roofline] {events:g} events in {len(w['steps'])} "
          f"steps (one per layer and step: {expected}), {secs:.6f} s",
          file=sys.stderr)
    if secs <= 0 or events != expected:
        return None
    tokens = sum(s["tokens"] for s in w["steps"])
    per_ops, per_bytes = flops.ssd_scan_per_token_layer(
        ctx.spec, ctx.traffic["seq"])
    layers = ctx.spec["n_layer"]
    t_ops = per_ops * tokens * layers / ctx.chips / ctx.peaks["bf16_flops_per_s"]
    t_bytes = per_bytes * tokens * layers / ctx.chips / ctx.peaks["hbm_bytes_per_s"]
    print(f"[ssd_scan_roofline] bound by "
          f"{'bytes' if t_bytes >= t_ops else 'operations'}", file=sys.stderr)
    return 100.0 * max(t_ops, t_bytes) / secs
