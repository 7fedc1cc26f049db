"""Sharded mixed-precision AdamW.

State per-leaf: {master fp32, mu fp32, nu fp32}; params stay in model dtype.
The state pytree mirrors the param pytree, so the FSDP/ZeRO sharding rules in
parallel/sharding.py apply verbatim (this is ZeRO-3 semantics under pjit: XLA
all-gathers weights for compute, reduce-scatters grads back to the shards).

The ElasWave VirtualCluster uses the same math on flattened per-layer vectors
(its ZeRO-1 shards) through `adam_update_flat_np`, the host mirror of
`adam_update_flat`.
"""
from __future__ import annotations

import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, NamedTuple, Optional, Tuple

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


#: elements of one block of the host AdamW: a block's fifteen elementwise
#: passes run over data the previous pass left in cache, and each pass is
#: long enough that the threads seldom wait for the GIL, which numpy holds
#: between passes.  The best of a sweep on a TPU v5e host
#: (``benchmarks/host_adam.py``, PERF.md): smaller blocks wait on the GIL
BLOCK = 1 << 20
#: below this many elements the whole vector is one block on the calling
#: thread (the CPU tests' models); at and above it the blocks are shared
#: among the pool's threads
THRESHOLD = 1 << 20
#: worker threads: every core the process may run on, up to 8, beyond which
#: the same sweep gained nothing
THREADS = max(1, min(len(os.sched_getaffinity(0)), 8))
_pool: Optional[ThreadPoolExecutor] = None


def adam_plan(n: int) -> Dict[str, int]:
    """``{"blocks", "threads"}`` the host AdamW uses over ``n`` elements."""
    if n < THRESHOLD:
        return {"blocks": 1, "threads": 1}
    blocks = max(1, -(-n // BLOCK))
    return {"blocks": blocks, "threads": min(THREADS, blocks)}


def _adam_blocks(g, st, out, lo: int, hi: int, block: int, step: int,
                 cfg: AdamConfig):
    """The op sequence of :func:`adam_update_flat` over elements
    ``[lo, hi)``, ``block`` elements at a time, into ``out``.  ``out`` may
    be ``st`` itself: every op is elementwise and a block's old master is
    read before its new one is written."""
    b1, b2 = np.float32(cfg.b1), np.float32(cfg.b2)
    c1, c2 = np.float32(1 - cfg.b1), np.float32(1 - cfg.b2)
    b1t = np.float32(1.0 - cfg.b1 ** step)
    b2t = np.float32(1.0 - cfg.b2 ** step)
    eps, wd = np.float32(cfg.eps), np.float32(cfg.weight_decay)
    lr = np.float32(cfg.lr)
    t = np.empty(min(block, hi - lo), np.float32)
    u = np.empty_like(t)
    for a in range(lo, hi, block):
        b = min(a + block, hi)
        tb, ub, gb = t[:b - a], u[:b - a], g[a:b]
        master, mu, nu = out["master"][a:b], out["mu"][a:b], out["nu"][a:b]
        np.multiply(st["mu"][a:b], b1, out=mu)
        np.multiply(gb, c1, out=tb)
        mu += tb
        np.multiply(st["nu"][a:b], b2, out=nu)
        np.multiply(gb, c2, out=tb)
        tb *= gb
        nu += tb
        np.divide(mu, b1t, out=ub)
        np.divide(nu, b2t, out=tb)
        np.sqrt(tb, out=tb)
        tb += eps
        ub /= tb
        np.multiply(st["master"][a:b], wd, out=tb)
        ub += tb
        ub *= lr
        np.subtract(st["master"][a:b], ub, out=master)


def adam_update_flat_np(grad_vec, st, step: int, cfg: AdamConfig, out=None):
    """Host-side (numpy) mirror of :func:`adam_update_flat`, bit-identical.

    IEEE basic ops (+, -, *, /, sqrt) are correctly rounded in both numpy
    and XLA's *eager* single-op kernels, so running the same op sequence in
    f32 produces identical bits — while avoiding the ~8 per-call dispatches
    and host<->device round-trips of the eager path.  (A *jitted* fused
    version is NOT equivalent: XLA contracts mul+add chains into FMAs.)
    Used by the VirtualCluster step and the SnapshotPool;
    bit-identity to the eager path is checked by
    ``tests/test_zero_and_fabric.py``.

    The flat vectors are updated in blocks of ``BLOCK`` elements, shared
    among ``THREADS`` threads from ``THRESHOLD`` elements on (numpy's
    ufuncs release the GIL); :func:`adam_plan` says how a size is split.
    ``out``, a dict of ``master``/``mu``/``nu`` float32 arrays, receives the
    new state, as numpy's ``out=`` does; it may be ``st`` itself (in place).
    Without it the new state is in fresh arrays and ``st`` is left intact.

    Returns the new state dict {master, mu, nu} (f32 numpy arrays).
    """
    global _pool
    g = np.asarray(grad_vec, dtype=np.float32).reshape(-1)
    st = {c: np.asarray(st[c], dtype=np.float32).reshape(-1)
          for c in ("master", "mu", "nu")}
    if out is None:
        out = {c: np.empty_like(g) for c in st}
    n = g.size
    plan = adam_plan(n)
    threads = plan["threads"]
    if threads == 1:
        _adam_blocks(g, st, out, 0, n, max(-(-n // plan["blocks"]), 1),
                     step, cfg)
    else:
        if _pool is None:
            _pool = ThreadPoolExecutor(THREADS, "host-adam")
        # each thread a contiguous run of whole blocks
        cuts = [BLOCK * (plan["blocks"] * k // threads)
                for k in range(threads)] + [n]
        list(_pool.map(lambda k: _adam_blocks(g, st, out, cuts[k],
                                              cuts[k + 1], BLOCK, step, cfg),
                       range(threads)))
    return {c: out[c] for c in ("master", "mu", "nu")}
