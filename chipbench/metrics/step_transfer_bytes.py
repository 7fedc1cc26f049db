"""Bytes a window step moves between host and device: tokens and sample
ids up, losses and per-item gradients down, the masters up as parameters
(``nbytes`` of the program's spans ``repro.step.inputs``, ``.fetch``,
``.writeback``; ``chipbench/program_spans.py``)."""
from chipbench import program_spans


def read(ctx):
    return program_spans.per_step(ctx, "nbytes", "step.inputs",
                                  "step.fetch", "step.writeback")
