"""Seconds a window step spends copying the step's losses and per-item
flat gradients from the device to the host (the program's span
``repro.step.fetch``; ``chipbench/program_spans.py``)."""
from chipbench import program_spans


def read(ctx):
    return program_spans.per_step(ctx, "seconds", "step.fetch")
