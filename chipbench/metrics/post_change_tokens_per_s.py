"""Tokens per second over the steps after the first step that follows the
event: the resharded job's rate once recovery and its first step are
behind it."""


def read(ctx):
    w = ctx.window
    end = w["marks"].get("first_step_after_event")
    if end is None:
        return None
    after = [s for s in w["steps"] if s["t0"] >= end]
    if not after:
        return None
    return sum(s["tokens"] for s in after) / (after[-1]["t1"] - end)
