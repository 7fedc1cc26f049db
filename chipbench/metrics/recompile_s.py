"""Host time of ``compile_step`` after the layout change, in the window
(the harness's span): tracing the new step program and fetching it from
the persistent compilation cache, where set-up left it."""


def read(ctx):
    w = ctx.window
    hits = [b - a for n, a, b in ctx.spans.items
            if n == "recompile_step" and w["t0"] <= a <= w["t1"]]
    return sum(hits) if hits else None
