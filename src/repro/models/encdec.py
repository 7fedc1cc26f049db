"""Encoder-decoder (Whisper-style) model.

The conv/mel frontend is a STUB per the assignment: ``input_specs`` supplies
precomputed frame embeddings [B, frames, d].  Encoder: bidirectional attention
blocks.  Decoder: causal self-attention + cross-attention + MLP.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from . import layers as L


def _init_xattn(key, cfg: ModelConfig) -> Dict[str, Any]:
    return L.init_attention(key, cfg)


def init_encdec_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    ks = jax.random.split(key, 6)
    dt = cfg.jnp_dtype

    def enc_block(k):
        k1, k2 = jax.random.split(k)
        return {"ln1": L.init_rmsnorm(cfg.d_model, dt),
                "attn": L.init_attention(k1, cfg),
                "ln2": L.init_rmsnorm(cfg.d_model, dt),
                "mlp": L.init_mlp(k2, cfg)}

    def dec_block(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {"ln1": L.init_rmsnorm(cfg.d_model, dt),
                "attn": L.init_attention(k1, cfg),
                "lnx": L.init_rmsnorm(cfg.d_model, dt),
                "xattn": _init_xattn(k2, cfg),
                "ln2": L.init_rmsnorm(cfg.d_model, dt),
                "mlp": L.init_mlp(k3, cfg)}

    ne = cfg.encoder_layers or cfg.num_layers
    nd = cfg.decoder_layers or cfg.num_layers
    return {
        "embed": L.init_embedding(ks[0], cfg),
        "enc_pos": (jax.random.normal(ks[1], (cfg.max_source_positions, cfg.d_model),
                                      jnp.float32) * 0.02).astype(dt),
        "encoder": jax.vmap(enc_block)(jax.random.split(ks[2], ne)),
        "enc_norm": L.init_rmsnorm(cfg.d_model, dt),
        "decoder": jax.vmap(dec_block)(jax.random.split(ks[3], nd)),
        "final_norm": L.init_rmsnorm(cfg.d_model, dt),
        "head": L.init_lm_head(ks[4], cfg),
    }


def encode(params, cfg: ModelConfig, frames, use_pallas: bool = False,
           remat: bool = False) -> jax.Array:
    """frames: [B, T, d] precomputed frame embeddings (frontend stub)."""
    B, T, _ = frames.shape
    x = frames.astype(cfg.jnp_dtype) + params["enc_pos"][None, :T, :]
    positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))

    def body(x, blkp):
        h = L.rmsnorm(blkp["ln1"], x, cfg.norm_eps, use_pallas=use_pallas)
        a, _ = L.apply_attention(blkp["attn"], cfg, h, positions,
                                 causal=False, use_pallas=use_pallas)
        x = x + a
        h = L.rmsnorm(blkp["ln2"], x, cfg.norm_eps, use_pallas=use_pallas)
        return x + L.apply_mlp(blkp["mlp"], cfg, h), None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    if cfg.scan_layers:
        x, _ = jax.lax.scan(body, x, params["encoder"])
    else:
        ne = cfg.encoder_layers or cfg.num_layers
        for i in range(ne):
            x, _ = body(x, jax.tree.map(lambda a: a[i], params["encoder"]))
    return L.rmsnorm(params["enc_norm"], x, cfg.norm_eps)


def decode(params, cfg: ModelConfig, tokens, enc_out,
           caches=None, cache_index=None, use_pallas: bool = False,
           remat: bool = False):
    """tokens: [B,S]; enc_out: [B,T,d]. Returns (logits, new_caches)."""
    B, S = tokens.shape
    x = L.embed(params["embed"], tokens)
    T = enc_out.shape[1]
    if cache_index is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    else:
        idx = jnp.broadcast_to(jnp.asarray(cache_index, jnp.int32), (B,))
        positions = idx[:, None] + jnp.arange(S)[None, :]
    enc_pos = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))

    def body(carry, xs):
        x = carry
        blkp, blkc = xs
        h = L.rmsnorm(blkp["ln1"], x, cfg.norm_eps, use_pallas=use_pallas)
        a, nc = L.apply_attention(blkp["attn"], cfg, h, positions,
                                  kv_cache=blkc, cache_index=cache_index,
                                  use_pallas=use_pallas)
        x = x + a
        # cross-attention over encoder output (non-causal, no cache needed:
        # enc_out K/V are recomputed — cheap at whisper scale).  use_pallas
        # only engages when S == T (the kernel needs square q/k), which the
        # _sdpa gate checks.
        h = L.rmsnorm(blkp["lnx"], x, cfg.norm_eps, use_pallas=use_pallas)
        Hh, hd = cfg.num_heads, cfg.head_dim
        xa = blkp["xattn"]
        q = L._project(xa, cfg, h, "q").reshape(B, S, Hh, hd)
        k = L._project(xa, cfg, enc_out, "k").reshape(B, T, cfg.num_kv_heads, hd)
        v = L._project(xa, cfg, enc_out, "v").reshape(B, T, cfg.num_kv_heads, hd)
        a = L._sdpa(q, k, v, causal=False, use_pallas=use_pallas)
        x = x + a.reshape(B, S, Hh * hd) @ blkp["xattn"]["wo"]
        h = L.rmsnorm(blkp["ln2"], x, cfg.norm_eps, use_pallas=use_pallas)
        return x + L.apply_mlp(blkp["mlp"], cfg, h), nc

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    xs = (params["decoder"], caches)
    if cfg.scan_layers:
        x, new_caches = jax.lax.scan(body, x, xs)
    else:
        nd = cfg.decoder_layers or cfg.num_layers
        outs = []
        for i in range(nd):
            x, out_i = body(x, jax.tree.map(lambda a: a[i], xs))
            outs.append(out_i)
        new_caches = None if outs[0] is None else jax.tree.map(
            lambda *ls: jnp.stack(ls), *outs)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return L.lm_logits(params["head"], x), (new_caches if caches is not None else None)


def init_decoder_caches(cfg: ModelConfig, batch: int, max_len: int):
    nd = cfg.decoder_layers or cfg.num_layers
    one = {"k": jnp.zeros((batch, max_len, cfg.num_kv_heads, cfg.head_dim), cfg.jnp_dtype),
           "v": jnp.zeros((batch, max_len, cfg.num_kv_heads, cfg.head_dim), cfg.jnp_dtype)}
    return jax.tree.map(lambda a: jnp.broadcast_to(a[None], (nd,) + a.shape), one)


def encdec_train_loss(params, cfg: ModelConfig, batch, rng_ctx=None,
                      use_pallas: bool = False, remat: bool = False):
    enc_out = encode(params, cfg, batch["frames"], use_pallas=use_pallas,
                     remat=remat)
    logits, _ = decode(params, cfg, batch["tokens"], enc_out,
                       use_pallas=use_pallas, remat=remat)
    from .transformer import softmax_xent
    return softmax_xent(logits[:, :-1], batch["labels"][:, 1:])


def encdec_decode_step(params, cfg: ModelConfig, tokens, enc_out, caches, cache_index):
    logits, new_caches = decode(params, cfg, tokens, enc_out,
                                caches=caches, cache_index=cache_index)
    return logits[:, -1:, :], new_caches
