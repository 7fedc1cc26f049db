"""Seconds the window's recovery spends on optimizer state: verifying the
ring snapshot's shards, the live remap onto the survivors, and layer
migration (the program's spans ``repro.recover.verify``, ``.remap``,
``.migration``; ``chipbench/program_spans.py``)."""
from chipbench import program_spans


def read(ctx):
    return program_spans.total(ctx, "seconds", "recover.verify",
                               "recover.remap", "recover.migration")
