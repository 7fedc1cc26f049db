"""Primitive layers shared by all model families.

Design notes
------------
* Pure-functional: ``init_*`` returns a param pytree, ``apply_*`` consumes it.
* **Content-addressed RNG** (ElasWave RNG-resharding, JAX-native): every random
  op derives its key as ``fold_in(fold_in(step_key, layer_id), sample_id)``.
  The mask depends only on (step, layer, sample) identity — never on which rank
  or micro-batch slot computes it — so any elastic re-partitioning reproduces
  bit-identical randomness.  See core/planners/rng.py.
* Attention supports GQA (kv-head broadcast), with a bias on the q/k/v
  projections where ``cfg.qkv_bias`` (Qwen2), and MLA (latent KV, deepseek-v3).
* KV caches are explicit pytrees so serve_step can be jitted/lowered.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig


# --------------------------------------------------------------------------
# RNG context (content-addressed randomness)
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class RngCtx:
    """Identity-addressed randomness for computation consistency."""
    step_key: Optional[jax.Array] = None      # fold_in(base_key, step)
    sample_ids: Optional[jax.Array] = None    # [batch] global sample ids
    deterministic: bool = True

    def layer(self, layer_id: int) -> "RngCtx":
        if self.deterministic or self.step_key is None:
            return self
        return dataclasses.replace(
            self, step_key=jax.random.fold_in(self.step_key, layer_id))


jax.tree_util.register_pytree_node(
    RngCtx,
    lambda c: ((c.step_key, c.sample_ids), c.deterministic),
    lambda det, xs: RngCtx(xs[0], xs[1], det),
)


def dropout(x: jax.Array, rate: float, ctx: RngCtx, op_id: int = 0) -> jax.Array:
    """Per-sample content-addressed dropout. x: [batch, seq, ...]."""
    if ctx.deterministic or rate <= 0.0 or ctx.step_key is None:
        return x
    key = jax.random.fold_in(ctx.step_key, op_id)

    def mask_one(sid):
        k = jax.random.fold_in(key, sid)
        return jax.random.bernoulli(k, 1.0 - rate, x.shape[1:])

    keep = jax.vmap(mask_one)(ctx.sample_ids)
    return jnp.where(keep, x / (1.0 - rate), 0.0).astype(x.dtype)


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------
def init_rmsnorm(d: int, dtype) -> Dict[str, Any]:
    return {"scale": jnp.ones((d,), dtype=jnp.float32)}


def rmsnorm(params, x, eps: float = 1e-5, use_pallas: bool = False):
    if use_pallas:
        from repro.kernels import ops as kops
        return kops.rmsnorm(x, params["scale"], eps=eps)
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * params["scale"]
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., seq, heads, head_dim]; positions: [..., seq]."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # [hd/2]
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., seq, hd/2]
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Dense init helper
# --------------------------------------------------------------------------
def _dense(key, shape, dtype, scale=None):
    fan_in = shape[0]
    scale = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(dtype)


# --------------------------------------------------------------------------
# Attention (GQA)
# --------------------------------------------------------------------------
def init_attention(key, cfg: ModelConfig) -> Dict[str, Any]:
    d, H, Hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = cfg.jnp_dtype
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense(ks[0], (d, H * hd), dt),
        "wk": _dense(ks[1], (d, Hkv * hd), dt),
        "wv": _dense(ks[2], (d, Hkv * hd), dt),
        "wo": _dense(ks[3], (H * hd, d), dt),
    }
    if cfg.qkv_bias:
        p.update(bq=jnp.zeros((H * hd,), dt), bk=jnp.zeros((Hkv * hd,), dt),
                 bv=jnp.zeros((Hkv * hd,), dt))
    return p


def _sdpa_chunked(q, k, v, causal: bool, chunk_q: int = 512,
                  chunk_kv: int = 1024, q_offset=None):
    """Online-softmax attention in pure jnp (flash semantics): peak live
    logits are [B, Hkv, rep, cq, ckv] instead of [B, H, S, S].  This is the
    XLA-lowered twin of kernels/flash_attention.py, used by the production
    path when cfg.attn_chunked (the Pallas kernel takes over on real TPU).

    q_offset: optional [B] per-sample position of q[:, 0] within the key
    sequence (prefill-into-cache path).
    """
    B, S, H, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    hd_v = v.shape[-1]                 # MLA: v head dim != qk head dim
    rep = H // Hkv
    cq = min(chunk_q, S)
    ckv = min(chunk_kv, T)
    # pad to multiples
    pad_q = (-S) % cq
    pad_kv = (-T) % ckv
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0))) if pad_kv else k
    vp = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0))) if pad_kv else v
    nq, nk = qp.shape[1] // cq, kp.shape[1] // ckv
    qb = qp.reshape(B, nq, cq, Hkv, rep, hd)
    kb = kp.reshape(B, nk, ckv, Hkv, hd)
    vb = vp.reshape(B, nk, ckv, Hkv, hd_v)
    scale = hd ** -0.5

    def q_block(qi, qblk):
        # qblk: [B, cq, Hkv, rep, hd]
        m0 = jnp.full((B, Hkv, rep, cq), -1e30, jnp.float32)
        l0 = jnp.zeros((B, Hkv, rep, cq), jnp.float32)
        acc0 = jnp.zeros((B, cq, Hkv, rep, hd_v), jnp.float32)

        def kv_step(carry, ki):
            m, l, acc = carry
            kblk, vblk = kb[:, ki], vb[:, ki]
            s = jnp.einsum("bqkrh,btkh->bkrqt", qblk.astype(jnp.float32),
                           kblk.astype(jnp.float32)) * scale
            rows = qi * cq + jax.lax.broadcasted_iota(jnp.int32, (cq, ckv), 0)
            cols = ki * ckv + jax.lax.broadcasted_iota(jnp.int32, (cq, ckv), 1)
            valid = (cols < T)[None]             # [1,cq,ckv]; mask KV padding
            if causal:
                if q_offset is None:
                    valid = valid & (rows >= cols)[None]
                else:
                    rows_b = q_offset[:, None, None] + rows[None]   # [B,cq,ckv]
                    valid = valid & (rows_b >= cols[None])
            s = jnp.where(valid[:, None, None], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + jnp.sum(p, axis=-1)
            acc_new = acc * alpha.transpose(0, 3, 1, 2)[..., None] + jnp.einsum(
                "bkrqt,btkh->bqkrh", p, vblk.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, acc0),
                                      jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
        return out.astype(q.dtype)

    outs = jax.lax.map(lambda i: q_block(i, qb[:, i]), jnp.arange(nq))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nq * cq, H, hd_v)
    return out[:, :S]


def _sdpa(q, k, v, causal: bool, q_offset=None, use_pallas: bool = False):
    """q: [B,S,H,hd]; k,v: [B,T,Hkv,hd]. GQA broadcast. Returns [B,S,H,hd].

    q_offset: optional [B] vector of per-sample positions of q[:,0] within
    the key sequence (decode-with-cache); None means q and k are aligned.
    """
    B, S, H, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    # Pallas kernel requires aligned square q/k (no cache offset, S == T);
    # covers training self-attention, causal or not (encoder blocks).
    if use_pallas and q_offset is None and S == T:
        from repro.kernels import ops as kops
        return kops.flash_attention(q, k, v, causal=causal)
    rep = H // Hkv
    qr = q.reshape(B, S, Hkv, rep, hd)
    logits = jnp.einsum("bskrh,btkh->bkrst", qr, k).astype(jnp.float32)
    logits *= hd ** -0.5
    if causal:
        if q_offset is None:
            mask = jnp.tril(jnp.ones((S, T), dtype=bool), k=T - S)
            mask = mask[None, None, None]                          # [1,1,1,S,T]
        else:
            qpos = q_offset[:, None] + jnp.arange(S)[None, :]      # [B,S]
            mask = qpos[..., None] >= jnp.arange(T)[None, None, :]  # [B,S,T]
            mask = mask[:, None, None, :, :]
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkrst,btkh->bskrh", probs, v)
    return out.reshape(B, S, H, v.shape[-1])


def _project(params, cfg: ModelConfig, x, which: str):
    """x @ w{which}, plus b{which} where the config has q/k/v bias: added
    before RoPE, so every path (train, prefill, decode, chunked) has it."""
    y = x @ params["w" + which]
    return y + params["b" + which] if cfg.qkv_bias else y


def apply_attention(params, cfg: ModelConfig, x, positions,
                    kv_cache: Optional[Dict] = None, cache_index=None,
                    causal: bool = True, use_pallas: bool = False,
                    ) -> Tuple[jax.Array, Optional[Dict]]:
    """x: [B,S,d].  If kv_cache given, append k/v at cache_index (decode)."""
    B, S, d = x.shape
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = _project(params, cfg, x, "q").reshape(B, S, H, hd)
    k = _project(params, cfg, x, "k").reshape(B, S, Hkv, hd)
    v = _project(params, cfg, x, "v").reshape(B, S, Hkv, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    new_cache = None
    if kv_cache is not None:
        idx = jnp.broadcast_to(jnp.asarray(cache_index, dtype=jnp.int32), (B,))
        ck = _scatter_seq(kv_cache["k"], k, idx)
        cv = _scatter_seq(kv_cache["v"], v, idx)
        new_cache = {"k": ck, "v": cv}
        if cfg.attn_chunked and S > 1:
            # prefill-into-cache: chunked path with per-sample offsets
            out = _sdpa_chunked(q, ck.astype(q.dtype), cv.astype(q.dtype),
                                causal=causal, chunk_q=cfg.attn_chunk_q,
                                chunk_kv=cfg.attn_chunk_kv, q_offset=idx)
        else:
            out = _sdpa(q, ck.astype(q.dtype), cv.astype(q.dtype),
                        causal=causal, q_offset=idx)
    elif cfg.attn_chunked:
        out = _sdpa_chunked(q, k, v, causal=causal, chunk_q=cfg.attn_chunk_q,
                            chunk_kv=cfg.attn_chunk_kv)
    else:
        out = _sdpa(q, k, v, causal=causal, use_pallas=use_pallas)
    return out.reshape(B, S, H * hd) @ params["wo"], new_cache


def _scatter_seq(cache, new, index):
    """cache: [B,T,...]; new: [B,S,...]; index: [B] per-sample write offset."""
    def one(c, n, i):
        return jax.lax.dynamic_update_slice_in_dim(c, n.astype(c.dtype), i, axis=0)
    return jax.vmap(one)(cache, new, index)


# --------------------------------------------------------------------------
# MLA attention (deepseek-v3)
# --------------------------------------------------------------------------
def init_mla(key, cfg: ModelConfig) -> Dict[str, Any]:
    d, H = cfg.d_model, cfg.num_heads
    r_kv, r_q = cfg.kv_lora_rank, cfg.q_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    dt = cfg.jnp_dtype
    ks = jax.random.split(key, 6)
    p = {
        "wkv_a": _dense(ks[0], (d, r_kv + dr), dt),
        "kv_norm": init_rmsnorm(r_kv, dt),
        "wkv_b": _dense(ks[1], (r_kv, H * (dn + dv)), dt),
        "wo": _dense(ks[2], (H * dv, d), dt),
    }
    if r_q:
        p["wq_a"] = _dense(ks[3], (d, r_q), dt)
        p["q_norm"] = init_rmsnorm(r_q, dt)
        p["wq_b"] = _dense(ks[4], (r_q, H * (dn + dr)), dt)
    else:
        p["wq"] = _dense(ks[5], (d, H * (dn + dr)), dt)
    return p


def apply_mla(params, cfg: ModelConfig, x, positions,
              kv_cache: Optional[Dict] = None, cache_index=None,
              ) -> Tuple[jax.Array, Optional[Dict]]:
    """Multi-head Latent Attention.  Latent cache = (c_kv, k_rope)."""
    B, S, d = x.shape
    H = cfg.num_heads
    dn, dr, dv, r_kv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    if cfg.q_lora_rank:
        q = rmsnorm(params["q_norm"], x @ params["wq_a"], cfg.norm_eps) @ params["wq_b"]
    else:
        q = x @ params["wq"]
    q = q.reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kv = x @ params["wkv_a"]                             # [B,S,r_kv+dr]
    c_kv, k_rope = kv[..., :r_kv], kv[..., r_kv:]
    c_kv = rmsnorm(params["kv_norm"], c_kv, cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]

    new_cache = None
    q_offset = None
    if kv_cache is not None:
        idx = jnp.broadcast_to(jnp.asarray(cache_index, dtype=jnp.int32), (B,))
        cc = _scatter_seq(kv_cache["c_kv"], c_kv, idx)
        cr = _scatter_seq(kv_cache["k_rope"], k_rope, idx)
        q_offset = idx
        new_cache = {"c_kv": cc, "k_rope": cr}
        c_kv, k_rope = cc.astype(x.dtype), cr.astype(x.dtype)

    if cfg.mla_absorb and kv_cache is not None:
        # Absorbed decode (§Perf): attention runs in the latent space.
        # scores = q_nope (W_kv_b^K)^T c_kv + q_rope k_rope; the O(T) latent
        # cache is never re-expanded to per-head K/V.
        kvb = params["wkv_b"].reshape(r_kv, H, dn + dv)
        qn_lat = jnp.einsum("bshn,rhn->bshr", q_nope.astype(jnp.float32),
                            kvb[..., :dn].astype(jnp.float32))  # [B,S,H,r]
        s_nope = jnp.einsum("bshr,btr->bhst", qn_lat,
                            c_kv.astype(jnp.float32))
        s_rope = jnp.einsum("bshd,btd->bhst", q_rope.astype(jnp.float32),
                            k_rope.astype(jnp.float32))
        scale = (dn + dr) ** -0.5
        logits = (s_nope + s_rope) * scale
        T = c_kv.shape[1]
        qpos = q_offset[:, None] + jnp.arange(S)[None, :]
        mask = qpos[..., None] >= jnp.arange(T)[None, None, :]
        logits = jnp.where(mask[:, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        o_lat = jnp.einsum("bhst,btr->bshr", probs,
                           c_kv.astype(jnp.float32))            # [B,S,H,r]
        out = jnp.einsum("bshr,rhn->bshn", o_lat,
                         kvb[..., dn:].astype(jnp.float32)).astype(x.dtype)
        return out.reshape(B, S, H * dv) @ params["wo"], new_cache

    # expand latent -> per-head keys/values
    kvb = params["wkv_b"].reshape(r_kv, H, dn + dv)
    k_nope = jnp.einsum("btr,rhn->bthn", c_kv, kvb[..., :dn])
    v = jnp.einsum("btr,rhn->bthn", c_kv, kvb[..., dn:])
    T = k_nope.shape[1]
    k_rope_b = jnp.broadcast_to(k_rope[:, :, None, :], (B, T, H, dr))
    k = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    qf = jnp.concatenate([q_nope, q_rope], axis=-1)
    if cfg.attn_chunked and (q_offset is None or S > 1):
        out = _sdpa_chunked(qf, k, v, causal=True, chunk_q=cfg.attn_chunk_q,
                            chunk_kv=cfg.attn_chunk_kv, q_offset=q_offset)
    else:
        out = _sdpa(qf, k, v, causal=True, q_offset=q_offset)
    return out.reshape(B, S, H * dv) @ params["wo"], new_cache


# --------------------------------------------------------------------------
# MLP
# --------------------------------------------------------------------------
def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Dict[str, Any]:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    dt = cfg.jnp_dtype
    ks = jax.random.split(key, 3)
    if cfg.activation == "relu2":          # nemotron: squared-ReLU, ungated
        return {"wi": _dense(ks[0], (d, ff), dt), "wo": _dense(ks[1], (ff, d), dt)}
    return {
        "wg": _dense(ks[0], (d, ff), dt),
        "wu": _dense(ks[1], (d, ff), dt),
        "wo": _dense(ks[2], (ff, d), dt),
    }


def apply_mlp(params, cfg: ModelConfig, x) -> jax.Array:
    if cfg.activation == "relu2":
        h = jax.nn.relu(x @ params["wi"])
        return (h * h) @ params["wo"]
    g = x @ params["wg"]
    act = jax.nn.gelu(g) if cfg.activation == "gelu" else jax.nn.silu(g)
    return (act * (x @ params["wu"])) @ params["wo"]


# --------------------------------------------------------------------------
# Embedding / head
# --------------------------------------------------------------------------
def init_embedding(key, cfg: ModelConfig) -> Dict[str, Any]:
    p = {"embedding": _dense(key, (cfg.vocab_size, cfg.d_model), cfg.jnp_dtype, scale=1.0)}
    return p


def embed(params, tokens):
    return jnp.take(params["embedding"], tokens, axis=0)


def init_lm_head(key, cfg: ModelConfig) -> Dict[str, Any]:
    return {"w": _dense(key, (cfg.d_model, cfg.vocab_size), cfg.jnp_dtype)}


def lm_logits(head_params, x):
    return x @ head_params["w"]
