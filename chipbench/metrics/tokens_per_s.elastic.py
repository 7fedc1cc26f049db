"""Tokens of every step completed in the window, over the window's time, in the elastic trainer's cells (``chipbench/readers.py``)."""
from chipbench.readers import tokens_per_s as read  # noqa: F401
