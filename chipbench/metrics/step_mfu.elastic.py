"""Model FLOP/s utilisation of the training step, in the elastic trainer's cells (``chipbench/readers.py``)."""
from chipbench.readers import step_mfu as read  # noqa: F401
