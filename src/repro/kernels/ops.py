"""Jitted public wrappers for the Pallas kernels.

The platform picks how a kernel runs: Mosaic compiles it when the default
backend is a TPU, and the Pallas interpreter runs it anywhere else (the CPU
tests).  ``interpret_mode`` makes that choice while the wrapper is traced,
never at import.  The wrappers adapt model-layer layouts (GQA head
broadcast, group broadcast) to the kernels' MHA/per-head forms, and validate
the layout contracts (head/group divisibility, unsupported initial state)
with crisp ``ValueError``s — shape checks are static, so they fire at trace
time even under ``jax.jit``.

Tolerance tiers
---------------
Pallas blocked softmax/scan is numerically equivalent but not bit-identical
to the plain-jnp references in ``kernels/ref.py`` (different reduction
order, online-softmax rescaling, per-chunk state passing).  Each kernel
declares its rtol/atol tier vs the reference here; ``TOLERANCE_TIERS`` is
the single source of truth consumed by ``tests/test_kernels.py``,
``core.invariants.KernelConsistencyChecker``, and the kernel-vs-ref gate in
``benchmarks/kernel_ref.py`` / CI.  Tiers are f32 bounds validated
empirically with margin over the deterministic test/fuzz corpus.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention_kernel
from .fused_adam import fused_adam_kernel
from .rmsnorm import rmsnorm_kernel
from .ssd_scan import ssd_scan_kernel


def interpret_mode() -> bool:
    """True unless the default backend is a TPU: Pallas TPU kernels lower
    through Mosaic only there.  Read at trace time."""
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# custom VJPs: Pallas forward, jnp-reference backward.
#
# ``pl.pallas_call`` has no autodiff rule, so to live in the jax.grad training
# hot path each kernel is wrapped in a custom_vjp whose backward pass
# differentiates the matching kernels/ref.py oracle, linearized at the saved
# inputs.  The forward activations are the kernel's (within TOLERANCE_TIERS
# of the oracle); the gradients are the oracle's exact jnp gradients.
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_mha(qf, kf, vf, causal, block_q, block_k):
    return flash_attention_kernel(qf, kf, vf, causal=causal, block_q=block_q,
                                  block_k=block_k, interpret=interpret_mode())


def _flash_mha_fwd(qf, kf, vf, causal, block_q, block_k):
    return _flash_mha(qf, kf, vf, causal, block_q, block_k), (qf, kf, vf)


def _flash_mha_bwd(causal, block_q, block_k, res, g):
    qf, kf, vf = res
    _, vjp = jax.vjp(
        lambda q, k, v: ref.mha_reference(q, k, v, causal=causal), qf, kf, vf)
    return vjp(g)


_flash_mha.defvjp(_flash_mha_fwd, _flash_mha_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rmsnorm_p(x, scale, eps):
    return rmsnorm_kernel(x, scale, eps=eps, interpret=interpret_mode())


def _rmsnorm_fwd(x, scale, eps):
    return _rmsnorm_p(x, scale, eps), (x, scale)


def _rmsnorm_bwd(eps, res, g):
    x, scale = res
    _, vjp = jax.vjp(lambda xx, ss: ref.rmsnorm_reference(xx, ss, eps=eps),
                     x, scale)
    return vjp(g)


_rmsnorm_p.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ssd_p(x, dt, A, Bh, Ch, chunk):
    return ssd_scan_kernel(x, dt, A, Bh, Ch, chunk=chunk,
                           interpret=interpret_mode())


def _ssd_fwd(x, dt, A, Bh, Ch, chunk):
    return _ssd_p(x, dt, A, Bh, Ch, chunk), (x, dt, A, Bh, Ch)


def _ssd_bwd(chunk, res, g):
    _, vjp = jax.vjp(lambda *a: ref.ssd_reference(*a)[0], *res)
    return vjp(g)


_ssd_p.defvjp(_ssd_fwd, _ssd_bwd)

#: Declared per-kernel f32 tolerance vs the ``kernels/ref.py`` oracle.
TOLERANCE_TIERS = {
    "flash_attention": {"rtol": 1e-4, "atol": 1e-5},
    "rmsnorm": {"rtol": 1e-5, "atol": 1e-6},
    "ssd_scan": {"rtol": 1e-4, "atol": 1e-5},
    "fused_adam": {"rtol": 1e-6, "atol": 1e-7},
}


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True,
                    block_q: int = 128, block_k: int = 128):
    """q: [B,S,H,hd]; k,v: [B,S,Hkv,hd] (GQA broadcast inside). -> [B,S,H,hd]."""
    B, S, H, hd = q.shape
    Hkv = k.shape[2]
    if Hkv <= 0 or H % Hkv != 0:
        raise ValueError(
            f"flash_attention: num_heads H={H} is not a multiple of "
            f"num_kv_heads Hkv={Hkv} — the GQA broadcast repeats each kv "
            f"head H//Hkv times and requires H % Hkv == 0")
    rep = H // Hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    o = _flash_mha(qf, kf, vf, causal, block_q, block_k)
    return o.reshape(B, H, S, hd).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("eps",))
def rmsnorm(x, scale, *, eps: float = 1e-5):
    return _rmsnorm_p(x, scale, eps)


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, A, B, C, *, chunk: int = 256, initial_state=None):
    """Mamba2 SSD, model-layer layout: B, C: [b,s,g,n] (groups).
    Returns (y, final_state=None) matching mamba.ssd_chunked's signature.

    The kernel always scans from a zero state (the training path); a caller
    resuming a chunked scan must use the jnp path — silently ignoring the
    state would return wrong results, so a non-``None`` state raises."""
    if initial_state is not None:
        raise ValueError(
            "ssd_scan: initial_state is not supported by the Pallas kernel "
            "(it always scans from a zero state); pass initial_state=None "
            "or use mamba.ssd_chunked with use_pallas=False for the "
            "resume-from-state (prefill/decode) path")
    b, s, h, p = x.shape
    g = B.shape[2]
    if g <= 0 or h % g != 0:
        raise ValueError(
            f"ssd_scan: num_heads h={h} is not a multiple of ngroups g={g} "
            f"— the group broadcast repeats each B/C group h//g times and "
            f"requires h % g == 0")
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=2)
    Ch = jnp.repeat(C, rep, axis=2)
    y = _ssd_p(x, dt, A, Bh, Ch, chunk)
    return y, None


def fused_adam(grad, master, mu, nu, *, step: int, b1: float = 0.9,
               b2: float = 0.95, eps: float = 1e-8, lr: float = 3e-4,
               weight_decay: float = 0.1):
    """Fused AdamW over flat f32 vectors -> (master, mu, nu).

    Same op sequence as ``optim.adam.adam_update_flat_np`` (the VirtualCluster
    hot-path oracle).  Deliberately NOT jitted: under an enclosing jit XLA may
    contract the mul+add chains into FMAs (the PR 2 finding that blocked the
    fused jnp version); the Pallas body keeps the written op order on TPU and
    stays within TOLERANCE_TIERS["fused_adam"] of the numpy oracle in
    interpret mode.  See kernels/fused_adam.py.
    """
    shapes = {"grad": grad.shape, "master": master.shape,
              "mu": mu.shape, "nu": nu.shape}
    if len({tuple(s) for s in shapes.values()}) != 1:
        raise ValueError(f"fused_adam: mismatched operand shapes {shapes}")
    b1t = 1.0 - b1 ** step
    b2t = 1.0 - b2 ** step
    return fused_adam_kernel(grad, master, mu, nu, b1=b1, b2=b2, eps=eps,
                             lr=lr, weight_decay=weight_decay, b1t=b1t,
                             b2t=b2t, interpret=interpret_mode())
