"""Pallas TPU kernels for the compute hot-spots (flash attention, Mamba2 SSD
chunk scan, rmsnorm) with jitted wrappers (ops.py) and pure-jnp oracles
(ref.py).  Interpreted on the CPU; compiled by Mosaic on a TPU."""
from . import ops, ref
