"""Full-model assembly for all decoder-only families.

A model = embed -> [segments] -> final_norm -> lm_head, where each segment is
(pattern of block types) x (repeats), applied with ``jax.lax.scan`` over
repeats so the lowered HLO stays compact for 61..126-layer configs.

Two parameter layouts are supported:
* **stacked** (default): per-pattern-position params with a leading `repeats`
  axis — used by the pjit/dry-run/serving paths.
* **per-layer list** (`init_layer_params` / `apply_single_layer`): one pytree
  per physical layer — used by the ElasWave VirtualCluster, where layers
  migrate between pipeline stages.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ATTN, ATTN_MOE, MAMBA, MAMBA_MOE, ModelConfig
from . import layers as L
from . import mamba as M
from . import moe as X


def _is_attn(blk: str) -> bool:
    return blk in (ATTN, ATTN_MOE)


def _is_moe(blk: str) -> bool:
    return blk in (ATTN_MOE, MAMBA_MOE)


def _maybe_seq_shard(x, cfg: ModelConfig):
    """SP-style activation constraint: shard the sequence dim over `model`
    between blocks, so XLA lowers TP boundary all-reduces as reduce-scatter +
    all-gather pairs (half the wire volume, overlappable)."""
    if not cfg.seq_shard_acts or not _mesh_in_scope():
        return x          # no mesh in scope (unit tests): nothing to shard
    from jax.sharding import PartitionSpec as P
    U = P.UNCONSTRAINED
    return jax.lax.with_sharding_constraint(x, P(U, "model", U))


def _mesh_in_scope() -> bool:
    """A mesh set by ``jax.set_mesh`` or entered as a ``with mesh:``
    context (the dry-run's form)."""
    from jax._src import mesh as mesh_lib
    return not (jax.sharding.get_abstract_mesh().empty
                and mesh_lib.thread_resources.env.physical_mesh.empty)


# --------------------------------------------------------------------------
# Block init / apply
# --------------------------------------------------------------------------
def _has_mlp(cfg: ModelConfig, blk: str) -> bool:
    """Pure-SSM blocks (mamba2, d_ff=0) are mixer-only — no MLP sublayer."""
    return _is_moe(blk) or cfg.d_ff > 0


def init_block(key, cfg: ModelConfig, blk: str) -> Dict[str, Any]:
    ks = jax.random.split(key, 2)
    p: Dict[str, Any] = {"ln1": L.init_rmsnorm(cfg.d_model, cfg.jnp_dtype)}
    if _is_attn(blk):
        p["attn"] = L.init_mla(ks[0], cfg) if cfg.use_mla else L.init_attention(ks[0], cfg)
    else:
        p["mamba"] = M.init_mamba(ks[0], cfg)
    if _has_mlp(cfg, blk):
        p["ln2"] = L.init_rmsnorm(cfg.d_model, cfg.jnp_dtype)
        if _is_moe(blk):
            p["moe"] = X.init_moe(ks[1], cfg)
        else:
            p["mlp"] = L.init_mlp(ks[1], cfg)
    return p


def apply_block(params, cfg: ModelConfig, blk: str, x, positions,
                rng_ctx: L.RngCtx, layer_id, cache=None, cache_index=None,
                use_pallas: bool = False):
    """Returns (x, new_cache, aux_loss)."""
    ctx = rng_ctx if rng_ctx.deterministic else L.RngCtx(
        step_key=jax.random.fold_in(rng_ctx.step_key, layer_id),
        sample_ids=rng_ctx.sample_ids, deterministic=False)
    h = L.rmsnorm(params["ln1"], x, cfg.norm_eps, use_pallas=use_pallas)
    new_cache = None
    if _is_attn(blk):
        if cfg.use_mla:
            a, new_cache = L.apply_mla(params["attn"], cfg, h, positions,
                                       kv_cache=cache, cache_index=cache_index)
        else:
            a, new_cache = L.apply_attention(params["attn"], cfg, h, positions,
                                             kv_cache=cache, cache_index=cache_index,
                                             use_pallas=use_pallas)
    else:
        a, new_cache = M.apply_mamba(params["mamba"], cfg, h, state=cache,
                                     use_pallas=use_pallas)
    x = x + L.dropout(a, cfg.dropout_rate, ctx, op_id=0)
    aux = jnp.zeros((), jnp.float32)
    if _has_mlp(cfg, blk):
        h = L.rmsnorm(params["ln2"], x, cfg.norm_eps, use_pallas=use_pallas)
        if _is_moe(blk):
            m, aux = X.apply_moe(params["moe"], cfg, h)
        else:
            m = L.apply_mlp(params["mlp"], cfg, h)
        x = x + L.dropout(m, cfg.dropout_rate, ctx, op_id=1)
    return x, new_cache, aux


def init_block_cache(cfg: ModelConfig, blk: str, batch: int, max_len: int):
    if _is_attn(blk):
        if cfg.use_mla:
            return {"c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), cfg.jnp_dtype),
                    "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), cfg.jnp_dtype)}
        return {"k": jnp.zeros((batch, max_len, cfg.num_kv_heads, cfg.head_dim), cfg.jnp_dtype),
                "v": jnp.zeros((batch, max_len, cfg.num_kv_heads, cfg.head_dim), cfg.jnp_dtype)}
    return M.init_mamba_state(cfg, batch)


# --------------------------------------------------------------------------
# Stacked (scan) model — pjit / dry-run / serving path
# --------------------------------------------------------------------------
def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    ks = jax.random.split(key, 4)
    params: Dict[str, Any] = {"embed": L.init_embedding(ks[0], cfg)}
    segs = []
    kseg = ks[1]
    for pat, rep in cfg.block_pattern():
        kseg, kuse = jax.random.split(kseg)
        pos_params = []
        for pi, blk in enumerate(pat):
            kblk = jax.random.fold_in(kuse, pi)
            stacked = jax.vmap(lambda k: init_block(k, cfg, blk))(
                jax.random.split(kblk, rep))
            pos_params.append(stacked)
        segs.append(pos_params)
    params["segments"] = segs
    params["final_norm"] = L.init_rmsnorm(cfg.d_model, cfg.jnp_dtype)
    if not cfg.tie_embeddings:
        params["head"] = L.init_lm_head(ks[2], cfg)
    return params


def param_shapes(cfg: ModelConfig):
    """ShapeDtypeStruct pytree of params without allocating (for dry-run)."""
    return jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))


def forward(params, cfg: ModelConfig, tokens, *,
            rng_ctx: Optional[L.RngCtx] = None,
            prefix_embeds=None, caches=None, cache_index=None,
            use_pallas: bool = False, remat: bool = False):
    """tokens: [B,S] -> (logits [B,S(,+P),V], new_caches, aux_loss).

    prefix_embeds: [B,P,d] precomputed modality embeddings (vlm/audio stub),
    prepended before token embeddings.
    """
    rng_ctx = rng_ctx or L.RngCtx()
    x = L.embed(params["embed"], tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    if cache_index is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    else:
        idx = jnp.broadcast_to(jnp.asarray(cache_index, jnp.int32), (B,))
        positions = idx[:, None] + jnp.arange(S)[None, :]

    aux_total = jnp.zeros((), jnp.float32)
    layer_base = 0
    new_caches = [] if caches is not None else None
    seg_caches = caches or [None] * len(params["segments"])

    for si, ((pat, rep), pos_params) in enumerate(
            zip(cfg.block_pattern(), params["segments"])):
        cache_in = seg_caches[si]

        def body(carry, xs):
            x, aux, lid = carry
            blkp, blkc = xs
            outc = []
            for pi, blk in enumerate(pat):
                c = blkc[pi] if blkc is not None else None
                fn = apply_block
                if remat:
                    # static: cfg, block-type, use_pallas (python values)
                    fn = jax.checkpoint(apply_block, static_argnums=(1, 2, 9),
                                        prevent_cse=False)
                x, nc, a = fn(blkp[pi], cfg, blk, x, positions, rng_ctx,
                              lid + pi, c, cache_index, use_pallas)
                x = _maybe_seq_shard(x, cfg)
                outc.append(nc)
                aux = aux + a
            outc = outc if blkc is not None else None
            return (x, aux, lid + len(pat)), outc

        xs = (pos_params, cache_in)
        if cfg.scan_layers:
            (x, aux_total, layer_base), out_caches = jax.lax.scan(
                body, (x, aux_total, jnp.int32(layer_base)), xs)
        else:
            # unrolled: exact per-layer cost analysis (scan bodies are counted
            # once by XLA; the dry-run's reduced-depth variants use this path)
            carry = (x, aux_total, jnp.int32(layer_base))
            outs = []
            for ri in range(rep):
                xs_i = jax.tree.map(lambda a: a[ri], xs)
                carry, out_i = body(carry, xs_i)
                outs.append(out_i)
            (x, aux_total, layer_base) = carry
            out_caches = None if outs[0] is None else jax.tree.map(
                lambda *ls: jnp.stack(ls), *outs)
        if new_caches is not None:
            new_caches.append(out_caches)

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps, use_pallas=use_pallas)
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["embedding"].T
    else:
        logits = L.lm_logits(params["head"], x)
    return logits, new_caches, aux_total


def init_caches(cfg: ModelConfig, batch: int, max_len: int):
    """Stacked caches matching the scan layout: per segment, per pattern pos,
    leading `repeats` axis."""
    caches = []
    for pat, rep in cfg.block_pattern():
        pos_caches = []
        for blk in pat:
            one = init_block_cache(cfg, blk, batch, max_len)
            stacked = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (rep,) + a.shape), one)
            pos_caches.append(stacked)
        caches.append(pos_caches)
    return caches


def cache_shapes(cfg: ModelConfig, batch: int, max_len: int):
    return jax.eval_shape(lambda: init_caches(cfg, batch, max_len))


# --------------------------------------------------------------------------
# Loss / steps
# --------------------------------------------------------------------------
def softmax_xent(logits, labels, mask=None):
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0] - logz
    if mask is None:
        return -jnp.mean(ll)
    return -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def train_loss(params, cfg: ModelConfig, batch, rng_ctx: Optional[L.RngCtx] = None,
               use_pallas: bool = False, remat: bool = False):
    logits, _, aux = forward(params, cfg, batch["tokens"], rng_ctx=rng_ctx,
                             prefix_embeds=batch.get("prefix_embeds"),
                             use_pallas=use_pallas, remat=remat)
    P = 0 if batch.get("prefix_embeds") is None else batch["prefix_embeds"].shape[1]
    tok_logits = logits[:, P:, :]
    loss = softmax_xent(tok_logits[:, :-1], batch["labels"][:, 1:])
    return loss + aux


def decode_step(params, cfg: ModelConfig, tokens, caches, cache_index,
                prefix_embeds=None):
    """One-token decode: tokens [B,1] -> (logits [B,1,V], new caches)."""
    logits, new_caches, _ = forward(params, cfg, tokens, caches=caches,
                                    cache_index=cache_index,
                                    prefix_embeds=prefix_embeds)
    return logits[:, -1:, :], new_caches


def prefill(params, cfg: ModelConfig, tokens, caches, prefix_embeds=None):
    """Prefill: write the whole prompt into the caches (index 0)."""
    logits, new_caches, _ = forward(params, cfg, tokens, caches=caches,
                                    cache_index=0, prefix_embeds=prefix_embeds)
    return logits[:, -1:, :], new_caches
