"""Readers that more than one metric shares: a metric of the same quantity
is split by the end-to-end metric it moves (``tokens_per_s.elastic``,
``tokens_per_s.plain``), and each of its files binds ``read`` to one of
these."""
from chipbench import flops


def tokens_per_s(ctx):
    """Tokens of every step completed in the window, over the window's time
    (from its start to the end of its last step)."""
    w = ctx.window
    if not w["steps"]:
        return None
    return sum(s["tokens"] for s in w["steps"]) / (w["t1"] - w["t0"])


def device_idle_share(ctx):
    """Share of the window in which the chip ran no operation (profiler
    trace: 1 - busy / window, averaged over the chips used)."""
    t = ctx.trace
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def step_mfu(ctx):
    """Model FLOP/s utilisation of the training step: forward and backward
    operations per token (``chipbench/flops.py``, nothing recomputed
    counted) times the tokens of the window's steps, over the window, the
    chips used and the chip's bf16 peak."""
    w = ctx.window
    if ctx.peaks is None or not w["steps"]:
        return None
    tokens = sum(s["tokens"] for s in w["steps"])
    ops = flops.model_flops_per_token(ctx.spec, ctx.traffic["seq"]) * tokens
    return 100.0 * ops / ((w["t1"] - w["t0"]) * ctx.chips
                          * ctx.peaks["bf16_flops_per_s"])
