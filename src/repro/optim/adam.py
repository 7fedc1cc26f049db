"""Sharded mixed-precision AdamW.

State per-leaf: {master fp32, mu fp32, nu fp32}; params stay in model dtype.
The state pytree mirrors the param pytree, so the FSDP/ZeRO sharding rules in
parallel/sharding.py apply verbatim (this is ZeRO-3 semantics under pjit: XLA
all-gathers weights for compute, reduce-scatters grads back to the shards).

The ElasWave VirtualCluster uses the same math through `adam_update_flat` on
flattened per-layer vectors (its ZeRO-1 shards).
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class AdamConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    master_weights: bool = True


def init_opt_state(params, cfg: AdamConfig):
    def leaf(p):
        st = {"mu": jnp.zeros(p.shape, jnp.float32),
              "nu": jnp.zeros(p.shape, jnp.float32)}
        if cfg.master_weights:
            st["master"] = p.astype(jnp.float32)
        return st
    return {"leaves": jax.tree.map(leaf, params), "step": jnp.zeros((), jnp.int32)}


def opt_state_shapes(params_shapes, cfg: AdamConfig):
    return jax.eval_shape(lambda p: init_opt_state(p, cfg), params_shapes)


def adam_update(params, grads, state, cfg: AdamConfig):
    step = state["step"] + 1
    b1t = 1.0 - cfg.b1 ** step.astype(jnp.float32)
    b2t = 1.0 - cfg.b2 ** step.astype(jnp.float32)

    def leaf(p, g, st):
        g = g.astype(jnp.float32)
        mu = cfg.b1 * st["mu"] + (1 - cfg.b1) * g
        nu = cfg.b2 * st["nu"] + (1 - cfg.b2) * g * g
        mhat = mu / b1t
        nhat = nu / b2t
        base = st.get("master", p.astype(jnp.float32))
        upd = mhat / (jnp.sqrt(nhat) + cfg.eps) + cfg.weight_decay * base
        new_master = base - cfg.lr * upd
        new_p = new_master.astype(p.dtype)
        out = {"mu": mu, "nu": nu}
        if "master" in st:
            out["master"] = new_master
        return new_p, out

    flat = jax.tree.map(leaf, params, grads, state["leaves"],
                        is_leaf=lambda x: isinstance(x, dict) and "mu" in x)
    new_params = jax.tree.map(lambda t: t[0], flat,
                              is_leaf=lambda x: isinstance(x, tuple))
    new_leaves = jax.tree.map(lambda t: t[1], flat,
                              is_leaf=lambda x: isinstance(x, tuple))
    return new_params, {"leaves": new_leaves, "step": step}


# ---- flat-vector variant (VirtualCluster ZeRO shards) ----------------------
def init_flat_state(vec: jnp.ndarray) -> dict:
    return {"master": vec.astype(jnp.float32),
            "mu": jnp.zeros_like(vec, dtype=jnp.float32),
            "nu": jnp.zeros_like(vec, dtype=jnp.float32)}


def adam_update_flat(grad_vec, st, step: int, cfg: AdamConfig):
    """Update one flattened shard.  Returns (new_param_vec_f32, new_state)."""
    g = grad_vec.astype(jnp.float32)
    b1t = 1.0 - cfg.b1 ** step
    b2t = 1.0 - cfg.b2 ** step
    mu = cfg.b1 * st["mu"] + (1 - cfg.b1) * g
    nu = cfg.b2 * st["nu"] + (1 - cfg.b2) * g * g
    upd = (mu / b1t) / (jnp.sqrt(nu / b2t) + cfg.eps) + cfg.weight_decay * st["master"]
    master = st["master"] - cfg.lr * upd
    return master, {"master": master, "mu": mu, "nu": nu}


def adam_update_flat_np(grad_vec, st, step: int, cfg: AdamConfig):
    """Host-side (numpy) mirror of :func:`adam_update_flat`, bit-identical.

    IEEE basic ops (+, -, *, /, sqrt) are correctly rounded in both numpy
    and XLA's *eager* single-op kernels, so running the same op sequence in
    f32 produces identical bits — while avoiding the ~8 per-call dispatches
    and host<->device round-trips of the eager path.  (A *jitted* fused
    version is NOT equivalent: XLA contracts mul+add chains into FMAs.)
    Used by the VirtualCluster fast path and the batched SnapshotPool;
    bit-identity to the eager path is checked by
    ``tests/test_zero_and_fabric.py``.

    Returns the new state dict {master, mu, nu} (f32 numpy arrays).
    """
    g = np.asarray(grad_vec, dtype=np.float32)
    b1t = np.float32(1.0 - cfg.b1 ** step)
    b2t = np.float32(1.0 - cfg.b2 ** step)
    # the op sequence of adam_update_flat, one rounding per op, written
    # in place over three temporaries: a fresh array per op costs more in
    # page faults than the arithmetic at a stage's size (~0.3 B elements)
    mu = np.multiply(st["mu"], np.float32(cfg.b1))
    t = np.multiply(g, np.float32(1 - cfg.b1))
    mu += t
    nu = np.multiply(st["nu"], np.float32(cfg.b2))
    np.multiply(g, np.float32(1 - cfg.b2), out=t)
    t *= g
    nu += t
    upd = np.divide(mu, b1t)
    np.divide(nu, b2t, out=t)
    np.sqrt(t, out=t)
    t += np.float32(cfg.eps)
    upd /= t
    np.multiply(st["master"], np.float32(cfg.weight_decay), out=t)
    upd += t
    upd *= np.float32(cfg.lr)
    master = np.subtract(st["master"], upd, out=upd)
    return {"master": master, "mu": mu, "nu": nu}
