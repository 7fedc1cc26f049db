"""Plain reference of a Qwen2-architecture language model (CodeQwen1.5).

embed -> L x [x + Attn(RMSNorm(x)); x + SwiGLU(RMSNorm(x))] -> RMSNorm ->
lm head.  Attention is causal softmax attention with rotary embeddings
(rotate-half, base ``rope_theta``) on q and k, scale head_dim ** -0.5; the
MLP is down(silu(gate(x)) * up(x)); k and v have ``num_key_value_heads``
heads, each shared by a group of query heads.  One departure, the
program's (the configuration's ``program_departures``): the q, k and v
projections have no bias.

The weights are laid out as the program's stacked train step takes them
(``param_shapes``) and made by ``chipbench/weights.py`` from the seed.
Attention runs in blocks of query rows and each layer is rematerialised,
so the float32 reference fits one chip beside its optimizer state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import weights
from chipbench.reference.common import F32, matmul_for, xent

Q_BLOCK = 512


def param_shapes(spec: dict):
    d, V, L = spec["hidden_size"], spec["vocab_size"], spec["num_hidden_layers"]
    H, Hkv = spec["num_attention_heads"], spec["num_key_value_heads"]
    hd, ff = spec["head_dim"], spec["intermediate_size"]
    dt = jnp.dtype(spec["dtype"])
    s = lambda shape, t=dt: jax.ShapeDtypeStruct(shape, t)
    scale = lambda: {"scale": s((L, d), F32)}
    block = {"ln1": scale(),
             "attn": {"wq": s((L, d, H * hd)), "wk": s((L, d, Hkv * hd)),
                      "wv": s((L, d, Hkv * hd)), "wo": s((L, H * hd, d))},
             "ln2": scale(),
             "mlp": {"wg": s((L, d, ff)), "wu": s((L, d, ff)),
                     "wo": s((L, ff, d))}}
    return {"embed": {"embedding": s((V, d))}, "segments": [[block]],
            "final_norm": {"scale": s((d,), F32)},
            "head": {"w": s((d, V))}}


def init_params(spec: dict, seed: int):
    return jax.tree.map(lambda a: a.astype(F32),
                        weights.make(param_shapes(spec), seed))


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def rope(x, theta):
    """x: [N, S, H, hd]; rotate-half rotary embedding at positions 0..S-1."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]    # [S, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """Causal attention, q/k/v: [N, S, H, hd], in blocks of query rows."""
    N, S, H, hd = q.shape
    rep = H // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    qb = min(Q_BLOCK, S)

    @jax.checkpoint
    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        s = jnp.einsum("nqhd,nkhd->nhqk", qi, k) * hd ** -0.5
        rows = i * qb + jnp.arange(qb)[:, None]
        s = jnp.where(rows >= jnp.arange(S)[None, :], s, -jnp.inf)
        return jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(block, jnp.arange(S // qb))             # [nb,N,qb,H,hd]
    return jnp.moveaxis(out, 0, 1).reshape(N, S, H, hd)


def loss_fn(spec: dict, precision: str = "f32"):
    mm = matmul_for(precision)
    eps, theta = spec["rms_norm_eps"], spec["rope_theta"]
    H, Hkv, hd = (spec["num_attention_heads"], spec["num_key_value_heads"],
                  spec["head_dim"])

    @jax.checkpoint
    def layer(x, p):
        N, S, d = x.shape
        h = rmsnorm(x, p["ln1"]["scale"], eps)
        a = p["attn"]
        q = rope(mm(h, a["wq"]).reshape(N, S, H, hd), theta)
        k = rope(mm(h, a["wk"]).reshape(N, S, Hkv, hd), theta)
        v = mm(h, a["wv"]).reshape(N, S, Hkv, hd)
        x = x + mm(attention(q, k, v).reshape(N, S, H * hd), a["wo"])
        h = rmsnorm(x, p["ln2"]["scale"], eps)
        m = p["mlp"]
        return x + mm(jax.nn.silu(mm(h, m["wg"])) * mm(h, m["wu"]), m["wo"])

    def loss(params, toks):
        x = jnp.take(params["embed"]["embedding"], toks, axis=0)
        stacked = params["segments"][0][0]
        for i in range(spec["num_hidden_layers"]):
            x = layer(x, jax.tree.map(lambda a: a[i], stacked))
        x = rmsnorm(x, params["final_norm"]["scale"], eps)
        return xent(mm(x, params["head"]["w"]), toks)
    return loss
