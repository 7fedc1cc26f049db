"""What every plain reference shares: the tokens, AdamW, leaf names and
norms, the float8 control, and a trainer that follows the program's steps.

Nothing here imports the program.  Everything runs in float32 at full
matmul precision (``jax.default_matmul_precision("highest")``): on a TPU a
float32 matmul is otherwise rounded to bfloat16 passes.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


# ---------------------------------------------------------------------------
# tokens: the sample-id-addressed stream, reproduced from its definition
# ---------------------------------------------------------------------------
def tokens(sample_ids, seq: int, vocab: int) -> np.ndarray:
    """Tokens of the given global sample ids, [n, seq] int32: a splitmix64
    hash of (sample id, position), reduced modulo the vocabulary."""
    ids = np.asarray(sample_ids, dtype=np.uint64)
    pos = np.arange(seq, dtype=np.uint64)[None, :]
    with np.errstate(over="ignore"):
        x = ids[:, None] * np.uint64(6364136223846793005) \
            + pos * np.uint64(1442695040888963407)
        x ^= x >> np.uint64(30)
        x *= np.uint64(0xbf58476d1ce4e5b9)
        x ^= x >> np.uint64(27)
        x *= np.uint64(0x94d049bb133111eb)
        x ^= x >> np.uint64(31)
    return (x % np.uint64(vocab)).astype(np.int32)


def step_tokens(step: int, global_batch: int, seq: int, vocab: int
                ) -> np.ndarray:
    """The global batch of ``step``: sample ids step*B .. step*B + B - 1."""
    start = step * global_batch
    return tokens(np.arange(start, start + global_batch), seq, vocab)


# ---------------------------------------------------------------------------
# leaves
# ---------------------------------------------------------------------------
def leaf_name(path) -> str:
    parts = []
    for k in path:
        for attr in ("key", "idx", "name"):
            if hasattr(k, attr):
                parts.append(str(getattr(k, attr)))
                break
        else:
            parts.append(str(k))
    return "/".join(parts)


def named_leaves(tree) -> Dict[str, Any]:
    return {leaf_name(p): a
            for p, a in jax.tree_util.tree_leaves_with_path(tree)}


@jax.jit
def _norms(tree):
    return jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(F32)))), tree)


@jax.jit
def _diff_norms(a, b):
    return jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x.astype(F32)
                                                 - y.astype(F32)))), a, b)


def norms(tree) -> Dict[str, float]:
    return {k: float(v) for k, v in named_leaves(jax.device_get(
        _norms(tree))).items()}


def diff_norms(a, b) -> Dict[str, float]:
    return {k: float(v) for k, v in named_leaves(jax.device_get(
        _diff_norms(a, b))).items()}


def np_norm(x: np.ndarray) -> float:
    x = np.asarray(x, dtype=np.float64).ravel()
    return float(np.sqrt(np.dot(x, x)))


# ---------------------------------------------------------------------------
# matmuls: full float32, or the float8 control
# ---------------------------------------------------------------------------
E4M3_MAX = 448.0


def fp8(x):
    """Round to float8 e4m3 with one scale per tensor (amax to 448), the
    way float8 training scales a tensor, and back to float32."""
    x = x.astype(F32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


@jax.custom_vjp
def fp8_matmul(a, b):
    return jnp.matmul(fp8(a), fp8(b))


def _fp8_fwd(a, b):
    return fp8_matmul(a, b), (fp8(a), fp8(b))


def _fp8_bwd(res, g):
    qa, qb = res
    qg = fp8(g)
    da = jnp.matmul(qg, qb.T)                       # b is a [in, out] weight
    db = jnp.einsum("...ij,...ik->jk", qa, qg)
    return da, db


fp8_matmul.defvjp(_fp8_fwd, _fp8_bwd)


def matmul_for(precision: str) -> Callable:
    """``f32``: the reference; ``fp8``: the control, every matmul's operands
    (and, in the backward pass, its cotangent) rounded to float8."""
    if precision == "f32":
        return lambda a, b: jnp.matmul(a.astype(F32), b.astype(F32))
    if precision == "fp8":
        return fp8_matmul
    raise ValueError(precision)


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------
def xent(logits, tokens_):
    """Mean next-token cross entropy: position t predicts token t+1."""
    logits = logits[:, :-1].astype(F32)
    labels = tokens_[:, 1:]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - ll)


def adamw(params, grads, mu, nu, step, opt: dict):
    """AdamW (decoupled weight decay, scaled by the learning rate) in f32.
    ``step`` counts from 1."""
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    lr, wd = opt["lr"], opt["weight_decay"]
    c1 = 1.0 - b1 ** step
    c2 = 1.0 - b2 ** step
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    params = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                  + wd * p), params, mu, nu)
    return params, mu, nu


def train(loss_fn: Callable, init: Callable, batches: List[np.ndarray],
          opt: dict, *, keep_batch: Optional[Callable] = None,
          other_grads: Optional[Dict[str, np.ndarray]] = None,
          keep_grads: bool = False) -> dict:
    """Follow ``len(batches)`` AdamW steps from ``init()``.

    Returns the loss of each step, the per-leaf norm of the first step's
    gradient, and the per-leaf norm of the parameters' change over all the
    steps.  With ``other_grads`` (another run's first gradient, per leaf),
    also the per-leaf norm of its difference from this first gradient
    (``grad_diff_norms``); with ``keep_grads``, this first gradient itself
    (``grads``, on the host).  ``keep_batch`` (a fault planted for
    calibration) maps each batch to the rows a faulty program would train
    on."""
    with jax.default_matmul_precision("highest"):
        params = init()

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def step_fn(params, mu, nu, toks, step):
            loss, grads = jax.value_and_grad(loss_fn)(params, toks)
            params, mu, nu = adamw(params, grads, mu, nu, step, opt)
            return params, mu, nu, loss, grads

        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        out: Dict[str, Any] = {"losses": []}
        for i, b in enumerate(batches):
            if keep_batch is not None:
                b = keep_batch(b)
            params, mu, nu, loss, grads = step_fn(
                params, mu, nu, jnp.asarray(b), jnp.float32(i + 1))
            out["losses"].append(float(loss))
            if i == 0:
                out["grad_norms"] = norms(grads)
                if other_grads is not None:
                    paths, treedef = jax.tree_util.tree_flatten_with_path(
                        grads)
                    other = jax.tree_util.tree_unflatten(
                        treedef, [jnp.asarray(other_grads[leaf_name(p)])
                                  .reshape(g.shape) for p, g in paths])
                    out["grad_diff_norms"] = diff_norms(grads, other)
                    del other
                if keep_grads:
                    out["grads"] = {k: np.asarray(v).ravel() for k, v in
                                    named_leaves(jax.device_get(grads)).items()}
            del grads
        del mu, nu
        out["change_norms"] = diff_norms(params, init())
    return out
