"""q/k/v bias (``ModelConfig.qkv_bias``, Qwen2's attention): the biased
attention against a hand-written grouped-query attention with the bias, in
every path (train, chunked, prefill and decode through the cache); its
parameter count and sharding; and, with the field off, parameter trees and
losses exactly as they were before the field existed."""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro import configs
from repro.models import layers as L
from repro.models import registry as R
from repro.models import transformer as T
from repro.parallel.sharding import param_pspecs


def _cfg(**kw):
    base = dict(num_heads=4, num_kv_heads=2, qkv_bias=True)
    base.update(kw)
    return R.tiny_config("dense", **base)


def _biased(cfg, seed=0):
    """Attention params with nonzero biases (init makes them zero)."""
    p = L.init_attention(jax.random.key(seed), cfg)
    ks = jax.random.split(jax.random.key(seed + 1), 3)
    for k, name in zip(ks, ("bq", "bk", "bv")):
        p[name] = jax.random.normal(k, p[name].shape, p[name].dtype)
    return p


def _hand_attention(p, cfg, x):
    """Causal GQA with q/k/v bias added before a rotate-half RoPE."""
    B, S, _ = x.shape
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    def rope(t):
        inv = 1.0 / cfg.rope_theta ** (np.arange(0, hd, 2) / hd)
        ang = np.arange(S)[:, None] * inv[None, :]
        cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
        t1, t2 = t[..., :hd // 2], t[..., hd // 2:]
        return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)

    q = rope((x @ p["wq"] + p["bq"]).reshape(B, S, H, hd))
    k = rope((x @ p["wk"] + p["bk"]).reshape(B, S, Hkv, hd))
    v = (x @ p["wv"] + p["bv"]).reshape(B, S, Hkv, hd)
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    s = jnp.where(np.tril(np.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    return o.reshape(B, S, H * hd) @ p["wo"]


@pytest.mark.parametrize("chunked", [False, True])
def test_biased_attention_matches_hand_written_gqa(chunked):
    cfg = _cfg(attn_chunked=chunked, attn_chunk_q=8, attn_chunk_kv=8)
    p = _biased(cfg)
    x = jax.random.normal(jax.random.key(5), (2, 16, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(16)[None], (2, 16))
    got, _ = L.apply_attention(p, cfg, x, pos)
    want = _hand_attention(p, cfg, x)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # the bias is there to be had: without it the result moves
    nob = dict(p, bq=0 * p["bq"], bk=0 * p["bk"], bv=0 * p["bv"])
    assert not np.allclose(L.apply_attention(nob, cfg, x, pos)[0], want,
                           atol=1e-3)


@pytest.mark.parametrize("chunked", [False, True])
def test_prefill_and_decode_through_the_cache_carry_the_bias(chunked):
    """Prefill of the first S-1 tokens, then one decode step: the logits
    of each equal the full forward's at the same positions."""
    cfg = _cfg(attn_chunked=chunked, attn_chunk_q=4, attn_chunk_kv=4)
    params = R.init_model(jax.random.key(0), cfg)
    attn = params["segments"][0][0]["attn"]
    for name in ("bq", "bk", "bv"):
        attn[name] = jax.random.normal(jax.random.key(len(name)),
                                       attn[name].shape)
    S = 12
    toks = jax.random.randint(jax.random.key(3), (2, S), 0, cfg.vocab_size)
    full, _, _ = T.forward(params, cfg, toks)
    caches = T.init_caches(cfg, 2, S)
    last, caches = T.prefill(params, cfg, toks[:, :-1], caches)
    np.testing.assert_allclose(last[:, 0], full[:, S - 2], rtol=1e-4,
                               atol=1e-4)
    step, _ = T.decode_step(params, cfg, toks[:, -1:], caches, S - 1)
    np.testing.assert_allclose(step[:, 0], full[:, S - 1], rtol=1e-4,
                               atol=1e-4)


def test_bias_leaves_init_zero_in_the_model_dtype():
    cfg = _cfg(dtype="bfloat16")
    p = L.init_attention(jax.random.key(0), cfg)
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    assert {k: (v.shape, v.dtype) for k, v in p.items() if k[0] == "b"} == {
        "bq": ((H * hd,), jnp.bfloat16), "bk": ((Hkv * hd,), jnp.bfloat16),
        "bv": ((Hkv * hd,), jnp.bfloat16)}
    assert not any(np.asarray(p[k], np.float32).any() for k in ("bq", "bk", "bv"))


def test_param_count_of_codeqwen_as_published():
    """32 x 202.9 M + 2 x 378.5 M: the published 7.25 B."""
    cfg = dataclasses.replace(configs.get_config("codeqwen1p5_7b"),
                              num_kv_heads=4, qkv_bias=True)
    layer = (2 * 4096 * 4096 + 2 * 4096 * 512 + 3 * 4096 * 13440
             + 2 * 4096 + (4096 + 2 * 512))
    assert cfg._block_params("attn") == layer == 202_912_768
    assert cfg.param_count() == 32 * layer + 2 * 92416 * 4096 \
        == 7_250_280_448
    shapes = R.model_param_shapes(dataclasses.replace(cfg, num_layers=1))
    held = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert held == dataclasses.replace(cfg, num_layers=1).param_count() + 4096


def test_bias_is_sharded_over_model_like_the_projections_out_dim():
    cfg = dataclasses.replace(configs.get_config("codeqwen1p5_7b"),
                              num_layers=2, num_kv_heads=4, qkv_bias=True)
    mesh = AbstractMesh((2, 2), ("data", "model"))
    specs = param_pspecs(cfg, mesh, R.model_param_shapes(cfg))
    attn = specs["segments"][0][0]["attn"]
    for w, b in (("wq", "bq"), ("wk", "bk"), ("wv", "bv")):
        assert attn[w] == P(None, "data", "model")
        assert attn[b] == P(None, "model")


# ---- with the field off, nothing moves -----------------------------------
#: (sha256 of the leaves' paths and bytes, loss as float.hex, leaf count)
#: of the tiny cuts below, recorded before ``qkv_bias`` existed.
BEFORE = {
    "mamba2_2p7b": ("1cf668b4c96fd3b11a9d53183fd39604a773f19586e5d43cb3d519e49f5c0118",
                    "0x1.245bc80000000p+5", 10),
    "codeqwen1p5_7b": ("2a07cf5385353cb729b6c01afbd3071b52954a44b331b35498bdfef218ca4afb",
                       "0x1.3e4af00000000p+2", 12),
}


def _tiny(name, **kw):
    cfg = configs.get_config(name)
    if name == "mamba2_2p7b":
        return dataclasses.replace(
            cfg, num_layers=2, d_model=64, ssm_state=16, ssm_headdim=16,
            ssm_chunk=32, vocab_size=96, dtype="float32", **kw)
    return dataclasses.replace(cfg, num_layers=2, d_model=64, num_heads=4,
                               num_kv_heads=2, head_dim=16, d_ff=96,
                               vocab_size=96, dtype="float32", **kw)


def _fingerprint(cfg, params=None):
    params = R.init_model(jax.random.key(7), cfg) if params is None else params
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_leaves_with_path(params)
    for path, a in leaves:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(a).tobytes())
    toks = (np.arange(2 * 64, dtype=np.int32).reshape(2, 64) * 37) \
        % cfg.vocab_size
    loss = jax.jit(R.make_train_loss(cfg))(params, {"tokens": toks,
                                                    "labels": toks})
    return h.hexdigest(), float(loss).hex(), len(leaves)


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_without_the_bias_trees_and_losses_are_as_before(name):
    cfg = _tiny(name)
    assert not cfg.qkv_bias
    assert _fingerprint(cfg) == BEFORE[name]


def test_zero_bias_gives_the_unbiased_loss_exactly():
    off = _tiny("codeqwen1p5_7b")
    on = dataclasses.replace(off, qkv_bias=True)
    params = R.init_model(jax.random.key(7), on)
    attn = params["segments"][0][0]["attn"]
    assert {"bq", "bk", "bv"} <= set(attn)
    unbiased = jax.tree.map(lambda a: a, params)
    for name in ("bq", "bk", "bv"):
        del unbiased["segments"][0][0]["attn"][name]
    assert _fingerprint(on, params)[1] == _fingerprint(off, unbiased)[1] \
        == BEFORE["codeqwen1p5_7b"][1]
