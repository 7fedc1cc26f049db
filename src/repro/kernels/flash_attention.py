"""Causal flash attention — Pallas TPU kernel.

Tiling: grid = (batch*heads, num_q_blocks, num_kv_blocks); the kv axis is the
innermost **sequential** grid dimension, so the online-softmax running state
(m, l, acc) lives in VMEM scratch and persists across kv steps.  Block shapes
are MXU-aligned (multiples of 128 on the matmul dims whenever the problem
size allows).  VMEM working set per program:
    q[bq, d] + k[bk, d] + v[bk, d] + acc[bq, d] + m/l[bq]  (fp32 acc)
e.g. bq=bk=128, d=128 -> ~4 * 128*128*4B ≈ 256 KiB — comfortably within the
~16 MiB v5e VMEM with double buffering.

GQA is handled by the ops.py wrapper (kv heads broadcast to q heads before
the call; the kernel itself is MHA).  Checked against ref.mha_reference in
interpret mode on the CPU and compiled by Mosaic on a TPU (``interpret`` is
chosen by ``ops.interpret_mode``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# f32 operands: ask Mosaic for a full f32 contraction (its default may round
# them to bf16), so the kernel meets its f32 tier on the chip as it does in
# the interpreter
_F32 = jax.lax.Precision.HIGHEST


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, causal: bool, sm_scale: float, block_q: int, block_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    def _body():
        q = q_ref[...].astype(jnp.float32)         # [bq, d]
        k = k_ref[...].astype(jnp.float32)         # [bk, d]
        v = v_ref[...].astype(jnp.float32)         # [bk, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=_F32,
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                            # [bq, bk]
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), precision=_F32,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    if causal:
        # skip blocks strictly above the diagonal
        pl.when(k_start <= q_start + block_q - 1)(_body)
    else:
        _body()

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, causal: bool = True,
                           sm_scale: float | None = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool):
    """q, k, v: [BH, S, d] (MHA, heads pre-folded into batch).  -> [BH, S, d]."""
    BH, S, d = q.shape
    assert k.shape == (BH, S, d) and v.shape == (BH, S, d)
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    sm_scale = sm_scale if sm_scale is not None else d ** -0.5
    grid = (BH, S // block_q, S // block_k)

    kernel = functools.partial(_flash_kernel, causal=causal, sm_scale=sm_scale,
                               block_q=block_q, block_k=block_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, d), q.dtype),
        scratch_shapes=[
            # m, l, acc persist across the sequential kv grid dimension
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
