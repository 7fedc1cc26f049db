"""Share of the window in which the chip ran no operation, in the elastic trainer's cells (``chipbench/readers.py``)."""
from chipbench.readers import device_idle_share as read  # noqa: F401
