"""Host time of ``detect_and_recover`` in the window: plan, communicator
edit, live remap, migration, dataflow (the harness's span)."""


def read(ctx):
    w = ctx.window
    hits = [b - a for n, a, b in ctx.spans.items
            if n == "detect_and_recover" and w["t0"] <= a <= w["t1"]]
    return sum(hits) if hits else None
