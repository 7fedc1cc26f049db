"""Mamba2 SSD chunk scan — Pallas TPU kernel.

The SSD recurrence is computed chunk-by-chunk: within a chunk the quadratic
(matmul-rich, MXU-friendly) form produces the intra-chunk output; the carried
state [p, n] lives in VMEM scratch and is advanced across the sequential
chunk grid dimension.  Tiling:

  grid = (batch, heads, num_chunks)   # chunks sequential (carry in scratch)
  VMEM blocks: x*dt[c, p], dt*A as [c, 1] and [1, c], B[c, n], C[c, n],
               out y[c, p], state[p, n]

For mamba2-2.7b (p=64, n=128, c=256) the working set is
  256*64 + 2*256*128 + 64*128 floats ≈ 0.4 MiB — VMEM-friendly; matmul dims
(c=256, n=128, p=64) are MXU-aligned on two of three axes.

Groups are pre-broadcast to heads by the ops.py wrapper.  Checked against
ref.ssd_reference (exact sequential recurrence) in interpret mode, and
compiled by Mosaic for a v5e chip in tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jax.lax.Precision.HIGHEST     # full f32 contraction, as in flash_attention


def _ssd_kernel(xdt_ref, dAc_ref, dAr_ref, B_ref, C_ref, y_ref, state_ref,
                *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    xdt = xdt_ref[...]                        # [c, p]  x * dt
    dA_col = dAc_ref[...]                     # [c, 1]  dt * A  (<= 0)
    dA_row = dAr_ref[...]                     # [1, c]  the same, as a row
    B = B_ref[...].astype(jnp.float32)        # [c, n]
    C = C_ref[...].astype(jnp.float32)        # [c, n]

    # within-chunk sums of dA as masked reductions (Mosaic has no cumsum).
    # Every dA <= 0, so each is a same-sign sum, exact to a few ulps of
    # itself: never a difference of two prefix sums, which cancels and
    # leaves an error of a few ulps of the prefix in every decay exponent.
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = li >= lj
    cum = jnp.sum(jnp.where(lower, dA_row, 0.0), axis=1, keepdims=True)
    rest = jnp.sum(jnp.where(lj > li, dA_row, 0.0), axis=1, keepdims=True)
    seg_total = jnp.sum(dA_row, axis=1, keepdims=True)            # [1, 1]
    # seg[i,j] = sum_{j<k<=i} dA_k: lower-triangular ones @ (dA_k if k > j)
    seg = jax.lax.dot_general(lower.astype(jnp.float32),
                              jnp.where(li > lj, dA_col, 0.0),
                              (((1,), (0,)), ((), ())), precision=_F32,
                              preferred_element_type=jnp.float32)  # [c, c]

    # ---- intra-chunk quadratic form ----
    # L[i,j] = exp(seg[i,j]) for i >= j else 0 (masked before the exp)
    Lm = jnp.exp(jnp.where(lower, seg, -1e30))              # [c, c]
    CB = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())), precision=_F32,
                             preferred_element_type=jnp.float32)  # [c, c]
    y_intra = jax.lax.dot_general(CB * Lm, xdt, (((1,), (0,)), ((), ())),
                                  precision=_F32,
                                  preferred_element_type=jnp.float32)

    # ---- contribution of the entering state ----
    state = state_ref[...]                                 # [p, n]
    y_inter = jax.lax.dot_general(C, state, (((1,), (1,)), ((), ())),
                                  precision=_F32,
                                  preferred_element_type=jnp.float32) \
        * jnp.exp(cum)                                     # [c, p]

    y_ref[...] = (y_intra + y_inter).astype(y_ref.dtype)

    # ---- advance the carried state ----
    # state' = exp(seg_total) * state + sum_i B_i dt_i decay_i x_i^T
    decay_to_end = jnp.exp(rest)                           # [c, 1]
    upd = jax.lax.dot_general(xdt * decay_to_end, B,
                              (((0,), (0,)), ((), ())),
                              precision=_F32,
                              preferred_element_type=jnp.float32)  # [p, n]
    state_ref[...] = jnp.exp(seg_total) * state + upd


def ssd_scan_kernel(x, dt, A, Bh, Ch, *, chunk: int = 256, interpret: bool):
    """x: [b,s,h,p]; dt: [b,s,h]; A: [h]; Bh, Ch: [b,s,h,n] (pre-broadcast).
    Returns y: [b,s,h,p] (final state not returned — training path)."""
    b, s, h, p = x.shape
    n = Bh.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    # layout: [b, h, s, ...] so the chunk axis is blockable per (b, h).  The
    # elementwise x*dt and dt*A run here in XLA, so the kernel reads no
    # per-head scalar and no lane-sparse [chunk] vector.
    dtt = jnp.moveaxis(dt, 1, 2).astype(jnp.float32)          # [b,h,s]
    xdt = jnp.moveaxis(x, 1, 2).astype(jnp.float32) * dtt[..., None]
    dA = dtt * A.astype(jnp.float32)[None, :, None]           # [b,h,s]
    Bt = jnp.moveaxis(Bh, 1, 2)                # [b,h,s,n]
    Ct = jnp.moveaxis(Ch, 1, 2)
    grid = (b, h, nc)
    y = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((None, None, chunk, 1), lambda bi, hi, ci: (bi, hi, ci, 0)),
            # one [1, chunk] row per chunk: as the whole of its last two
            # dims, a legal block for any chunk (a [1, s] slice of it is
            # not, unless chunk % 128 == 0)
            pl.BlockSpec((None, None, None, 1, chunk),
                         lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((None, None, chunk, n), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((None, None, chunk, n), lambda bi, hi, ci: (bi, hi, ci, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xdt, dA[..., None], dA.reshape(b, h, nc, 1, chunk), Bt, Ct)
    return jnp.moveaxis(y, 2, 1)               # [b,s,h,p]
