"""Seconds a window step spends in the host optimizer: the weighted
accumulation of the items' gradients, each stage's AdamW over its flat
state, and the write-back of the masters to the device's parameters (the
program's spans ``repro.step.accumulate``, ``.adam``, ``.writeback``;
``chipbench/program_spans.py``)."""
from chipbench import program_spans


def read(ctx):
    return program_spans.per_step(ctx, "seconds", "step.accumulate",
                                  "step.adam", "step.writeback")
