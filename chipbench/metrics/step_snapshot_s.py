"""Seconds a window step spends in the ring snapshot: the holders' host
AdamW over the shipped gradient shards and the CRC32 stamps (the program's
span ``repro.step.snapshot``; ``chipbench/program_spans.py``)."""
from chipbench import program_spans


def read(ctx):
    return program_spans.per_step(ctx, "seconds", "step.snapshot")
