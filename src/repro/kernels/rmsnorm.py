"""RMSNorm — Pallas TPU kernel.

Row-blocked: grid over row tiles; each program normalizes [block_rows, d] in
VMEM (d is the lane dimension, padded to 128 by the compiler).  fp32 math,
cast back to the input dtype — exactly matching ref.rmsnorm_reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) * s_ref[...]).astype(o_ref.dtype)


#: f32 bytes of one row block.  The input and output blocks are double-
#: buffered and the body holds a few f32 temporaries of the block's size, so
#: 1 MiB keeps a program well inside v5e's 16 MiB of scoped VMEM (256 rows of
#: d=4096 in f32 ran out of it).
_BLOCK_F32_BYTES = 1 << 20


def rmsnorm_kernel(x, scale, *, eps: float = 1e-5, interpret: bool):
    """x: [..., d]; scale: [d]."""
    orig_shape = x.shape
    d = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    block_rows = min(max(8, _BLOCK_F32_BYTES // (4 * d) // 8 * 8), rows)
    # pad rows to a multiple of block_rows
    pad = (-rows) % block_rows
    if pad:
        x2 = jnp.concatenate([x2, jnp.zeros((pad, d), x.dtype)], axis=0)
    grid = (x2.shape[0] // block_rows,)
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=interpret,
        name="rmsnorm",
    )(x2, scale.astype(jnp.float32))
    if pad:
        out = out[:rows]
    return out.reshape(orig_shape)
