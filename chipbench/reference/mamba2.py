"""Plain reference of a Mamba2 language model (arXiv:2405.21060).

embed -> n_layer x [x + Mamba2(RMSNorm(x))] -> RMSNorm -> lm head, each
Mamba2 mixer as published: one input projection to (z, x, B, C, dt), a
causal depthwise convolution and SiLU over (x, B, C), dt = softplus(dt +
dt_bias), A = -exp(A_log), the SSD scan with the skip D, RMSNorm of
y * SiLU(z) (norm before the gate: no), and the output projection.  The
scan is the paper's minimal chunked form (``ssd_minimal_discrete``), exact
up to float32 round-off.  Two departures, both the program's (the
configuration's ``program_departures``): the convolution has no bias, and
the head is a matrix of its own, not the tied embedding.

``init_params`` is the elastic trainer's documented initialisation,
re-made from the seed: it takes no weight from the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.common import F32, matmul_for, xent


def _dims(spec):
    d = spec["d_model"]
    di = spec["expand"] * d
    n, g = spec["d_state"], spec["ngroups"]
    H = di // spec["headdim"]
    return d, di, n, g, H


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, dtype=F32) * scale).astype(dtype)


def init_params(spec: dict, seed: int):
    """The weights as the elastic trainer makes them from ``seed``, rounded
    to the configuration's dtype and returned in float32."""
    d, di, n, g, H = _dims(spec)
    V, L, k = spec["vocab_size"], spec["n_layer"], spec["d_conv"]
    dt = jnp.dtype(spec["dtype"])
    ks = jax.random.split(jax.random.key(seed + 1), L + 2)
    ones = lambda m: {"scale": jnp.ones((m,), F32)}
    layers = []
    for i in range(L):
        kb = jax.random.split(ks[1 + i], 2)
        km = jax.random.split(kb[0], 4)
        conv_dim = di + 2 * g * n
        layers.append({
            "ln1": ones(d),
            "mamba": {
                "in_proj": _normal(km[0], (d, 2 * di + 2 * g * n + H),
                                   d ** -0.5, dt),
                "conv_w": _normal(km[1], (k, conv_dim), k ** -0.5, dt),
                "A_log": jnp.log(jnp.linspace(1.0, 16.0, H, dtype=F32)),
                "D": jnp.ones((H,), F32),
                "dt_bias": jnp.zeros((H,), F32),
                "out_norm": ones(di),
                "out_proj": _normal(km[2], (di, d), di ** -0.5, dt),
            }})
    k1, _ = jax.random.split(ks[L + 1])
    params = {"stem": {"embed": {"embedding": _normal(ks[0], (V, d), 1.0, dt)}},
              "layers": layers,
              "head": {"final_norm": ones(d),
                       "head": {"w": _normal(k1, (d, V), d ** -0.5, dt)}}}
    return jax.tree.map(lambda a: a.astype(F32), params)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def segsum(x):
    """x: [..., T] -> [..., T, T], out[i, j] = sum_{j < k <= i} x_k for
    i >= j, -inf above the diagonal."""
    T = x.shape[-1]
    xx = jnp.broadcast_to(x[..., None, :], x.shape + (T,))    # [..., i, k]
    xx = jnp.swapaxes(xx, -1, -2)                             # [..., k, j]
    below = jnp.tril(jnp.ones((T, T), bool), -1)
    xx = jnp.where(below, xx, 0.0)
    out = jnp.cumsum(xx, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), out, -jnp.inf)


def ssd(X, A, B, C, block: int):
    """Minimal discrete SSD.  X: [b,l,h,p] (x * dt); A: [b,l,h] (A * dt);
    B, C: [b,l,h,n].  Returns Y: [b,l,h,p]."""
    b, l, h, p = X.shape
    c = l // block
    r = lambda t: t.reshape((b, c, block) + t.shape[2:])
    X, A, B, C = r(X), r(A), r(B), r(C)
    A = jnp.moveaxis(A, 3, 1)                                 # [b,h,c,l]
    A_cum = jnp.cumsum(A, axis=-1)
    Lm = jnp.exp(segsum(A))                                   # [b,h,c,l,s]
    Y_diag = jnp.einsum("bclhn,bcshn,bhcls,bcshp->bclhp", C, B, Lm, X)
    decay_states = jnp.exp(A_cum[..., -1:] - A_cum)           # [b,h,c,l]
    states = jnp.einsum("bclhn,bhcl,bclhp->bchpn", B, decay_states, X)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    decay_chunk = jnp.exp(segsum(jnp.pad(A_cum[..., -1], ((0, 0), (0, 0),
                                                          (1, 0)))))
    new_states = jnp.einsum("bhzc,bchpn->bzhpn", decay_chunk, states)
    states = new_states[:, :-1]
    Y_off = jnp.einsum("bclhn,bchpn,bhcl->bclhp", C, states, jnp.exp(A_cum))
    return (Y_diag + Y_off).reshape(b, l, h, p)


def mixer(p, x, spec, mm):
    d, di, n, g, H = _dims(spec)
    N, S, _ = x.shape
    zxbcdt = mm(x, p["in_proj"])
    z = zxbcdt[..., :di]
    xBC = zxbcdt[..., di:2 * di + 2 * g * n]
    dt = zxbcdt[..., 2 * di + 2 * g * n:]
    k = p["conv_w"].shape[0]
    xp = jnp.pad(xBC, ((0, 0), (k - 1, 0), (0, 0)))
    xBC = jax.nn.silu(sum(xp[:, i:i + S] * p["conv_w"][i] for i in range(k)))
    xs = xBC[..., :di].reshape(N, S, H, spec["headdim"])
    Bv = xBC[..., di:di + g * n].reshape(N, S, g, n)
    Cv = xBC[..., di + g * n:].reshape(N, S, g, n)
    Bh = jnp.repeat(Bv, H // g, axis=2)
    Ch = jnp.repeat(Cv, H // g, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"])                   # [N,S,H]
    A = -jnp.exp(p["A_log"])
    block = min(spec["chunk_size"], S)
    y = ssd(xs * dt[..., None], A * dt, Bh, Ch, block)
    y = y + xs * p["D"][:, None]
    y = y.reshape(N, S, di)
    y = rmsnorm(y * jax.nn.silu(z), p["out_norm"]["scale"],
                spec["rms_norm_eps"])
    return mm(y, p["out_proj"])


def loss_fn(spec: dict, precision: str = "f32"):
    """``loss(params, tokens [N, S]) -> mean next-token cross entropy``."""
    mm = matmul_for(precision)
    eps = spec["rms_norm_eps"]

    def loss(params, toks):
        x = jnp.take(params["stem"]["embed"]["embedding"], toks, axis=0)
        for lp in params["layers"]:
            x = x + mixer(lp["mamba"], rmsnorm(x, lp["ln1"]["scale"], eps),
                          spec, mm)
        x = rmsnorm(x, params["head"]["final_norm"]["scale"], eps)
        return xent(mm(x, params["head"]["head"]["w"]), toks)
    return loss
