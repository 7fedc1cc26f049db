"""Fused AdamW — Pallas TPU kernel with controlled arithmetic order.

PR 2 rejected a jitted fused Adam: XLA contracts the ``b1*mu + (1-b1)*g``
mul+add chains into FMAs, breaking bit-identity with the host-numpy oracle
(``optim.adam.adam_update_flat_np``).  A Pallas kernel controls the
arithmetic order instead: on TPU each jnp op in the kernel body lowers to a
distinct Mosaic VPU op (no cross-statement FMA contraction).  Off the TPU
the Pallas interpreter still compiles the body through XLA, so the result
is within ~1 ulp per op of the numpy oracle rather than bit-identical —
checked against ``optim.adam.adam_update_flat_np`` under
``ops.TOLERANCE_TIERS["fused_adam"]`` (~10x observed margin) in
tests/test_kernels.py and timed by ``benchmarks/kernel_ref.py``.  On the
chip ``chip_smoke.py`` holds the Mosaic build to the same tier.

First cut: a bench/oracle kernel, NOT wired into the VirtualCluster hot
path (the host-numpy fused update stays the production path; its bit
identity with the seed is the stronger contract).  The bias-correction
terms ``b1t``/``b2t`` are baked in as compile-time constants, so each
optimizer step traces a fresh kernel — fine for validation, one more reason
it stays off the hot path for now.

Layout: the flat vector is padded to a multiple of 128 lanes and reshaped
[rows, 128]; the grid tiles rows, mirroring kernels/rmsnorm.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANES = 128


def _fused_adam_body(g_ref, m_ref, mu_ref, nu_ref, m_out, mu_out, nu_out, *,
                     b1: float, b2: float, eps: float, lr: float,
                     weight_decay: float, b1t: float, b2t: float):
    g = g_ref[...]
    master = m_ref[...]
    # exact op sequence of adam_update_flat_np — do not reassociate
    mu = jnp.float32(b1) * mu_ref[...] + jnp.float32(1.0 - b1) * g
    nu = jnp.float32(b2) * nu_ref[...] + jnp.float32(1.0 - b2) * g * g
    upd = (mu / jnp.float32(b1t)) / (jnp.sqrt(nu / jnp.float32(b2t))
                                     + jnp.float32(eps)) \
        + jnp.float32(weight_decay) * master
    m_out[...] = master - jnp.float32(lr) * upd
    mu_out[...] = mu
    nu_out[...] = nu


def fused_adam_kernel(grad, master, mu, nu, *, b1: float, b2: float,
                      eps: float, lr: float, weight_decay: float,
                      b1t: float, b2t: float, block_rows: int = 256,
                      interpret: bool):
    """grad/master/mu/nu: flat f32 [n]. Returns (master, mu, nu), f32 [n]."""
    n = grad.size
    cols = min(_LANES, max(n, 1))
    pad = (-n) % cols

    def prep(v):
        v = jnp.asarray(v, jnp.float32).reshape(-1)
        if pad:
            v = jnp.concatenate([v, jnp.zeros((pad,), jnp.float32)])
        return v.reshape(-1, cols)

    g2, m2, mu2, nu2 = prep(grad), prep(master), prep(mu), prep(nu)
    rows = g2.shape[0]
    block_rows = min(block_rows, rows)
    rpad = (-rows) % block_rows
    if rpad:
        z = jnp.zeros((rpad, cols), jnp.float32)
        g2, m2, mu2, nu2 = (jnp.concatenate([v, z]) for v in (g2, m2, mu2, nu2))
    grid = (g2.shape[0] // block_rows,)
    spec = pl.BlockSpec((block_rows, cols), lambda i: (i, 0))
    shape = jax.ShapeDtypeStruct(g2.shape, jnp.float32)
    out_m, out_mu, out_nu = pl.pallas_call(
        functools.partial(_fused_adam_body, b1=b1, b2=b2, eps=eps, lr=lr,
                          weight_decay=weight_decay, b1t=b1t, b2t=b2t),
        grid=grid,
        in_specs=[spec, spec, spec, spec],
        out_specs=[spec, spec, spec],
        out_shape=[shape, shape, shape],
        interpret=interpret,
    )(g2, m2, mu2, nu2)

    def unprep(v):
        return v.reshape(-1)[:n]

    return unprep(out_m), unprep(out_mu), unprep(out_nu)
