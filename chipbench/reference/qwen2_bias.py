"""Plain reference of a Qwen2-architecture language model as CodeQwen1.5-7B
is published: ``reference/qwen2.py``'s model (whose norm, rotary embedding
and attention it imports) with the bias on the q, k and v projections,
added before the rotary embedding.  The output projection has none.

The weights are laid out as the program's stacked train step takes them
(``param_shapes``) and made by ``chipbench/weights.py`` from the seed.
``init_params`` places each float32 leaf over the configuration's
``chips_per_layer`` chips (``shardings``: a NamedSharding on a
one-axis mesh).  That is placement only: the same jnp program, partitioned
by XLA; the float32 state (16 bytes a parameter with the gradient and
AdamW's moments) would not fit one chip.  Each layer is rematerialised,
attention runs in blocks of query rows, and the head and the cross entropy
run over one sequence at a time (a row block), so the logits of one
sequence are live at once.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chipbench import weights
from chipbench.reference import qwen2
from chipbench.reference.common import F32, leaf_name, matmul_for, xent

AXIS = "chips"
#: The axis of each leaf that is spread over the chips (the out-dimension
#: of an input projection, the in-dimension of an output one, the
#: vocabulary); a leaf not named here, or whose axis does not divide, is
#: held whole on every chip.
SPREAD = {"embedding": 0, "wq": -1, "wk": -1, "wv": -1, "bq": -1, "bk": -1,
          "bv": -1, "wg": -1, "wu": -1, "wo": -2, "w": -1}


def param_shapes(spec: dict):
    shapes = qwen2.param_shapes(spec)
    L, H, Hkv, hd = (spec["num_hidden_layers"], spec["num_attention_heads"],
                     spec["num_key_value_heads"], spec["head_dim"])
    dt = jnp.dtype(spec["dtype"])
    shapes["segments"][0][0]["attn"].update(
        bq=jax.ShapeDtypeStruct((L, H * hd), dt),
        bk=jax.ShapeDtypeStruct((L, Hkv * hd), dt),
        bv=jax.ShapeDtypeStruct((L, Hkv * hd), dt))
    return shapes


def shardings(spec: dict):
    """Where each float32 leaf lives: spread over the first
    ``chips_per_layer`` devices along its ``SPREAD`` axis."""
    devices = jax.devices()[:spec["chips_per_layer"]]
    mesh = Mesh(np.array(devices), (AXIS,))

    def place(path, sd):
        parts = [None] * sd.ndim
        axis = SPREAD.get(leaf_name(path).rsplit("/", 1)[-1])
        if axis is not None and sd.shape[axis] % len(devices) == 0:
            parts[axis] = AXIS
        return NamedSharding(mesh, P(*parts))
    return jax.tree_util.tree_map_with_path(place, param_shapes(spec))


def init_params(spec: dict, seed: int):
    shapes = param_shapes(spec)
    return jax.jit(lambda: jax.tree.map(lambda a: a.astype(F32),
                                        weights.make(shapes, seed)),
                   out_shardings=shardings(spec))()


def loss_fn(spec: dict, precision: str = "f32"):
    mm = matmul_for(precision)
    eps, theta = spec["rms_norm_eps"], spec["rope_theta"]
    H, Hkv, hd = (spec["num_attention_heads"], spec["num_key_value_heads"],
                  spec["head_dim"])

    @jax.checkpoint
    def layer(x, p):
        N, S, d = x.shape
        h = qwen2.rmsnorm(x, p["ln1"]["scale"], eps)
        a = p["attn"]
        q = qwen2.rope((mm(h, a["wq"]) + a["bq"]).reshape(N, S, H, hd), theta)
        k = qwen2.rope((mm(h, a["wk"]) + a["bk"]).reshape(N, S, Hkv, hd),
                       theta)
        v = (mm(h, a["wv"]) + a["bv"]).reshape(N, S, Hkv, hd)
        x = x + mm(qwen2.attention(q, k, v).reshape(N, S, H * hd), a["wo"])
        h = qwen2.rmsnorm(x, p["ln2"]["scale"], eps)
        m = p["mlp"]
        return x + mm(jax.nn.silu(mm(h, m["wg"])) * mm(h, m["wu"]), m["wo"])

    @jax.checkpoint
    def sequence_loss(x, toks, w):
        """One sequence's mean next-token cross entropy."""
        return xent(mm(x[None], w), toks[None])

    def loss(params, toks):
        x = jnp.take(params["embed"]["embedding"], toks, axis=0)
        x, _ = jax.lax.scan(lambda x, p: (layer(x, p), None), x,
                            params["segments"][0][0])
        x = qwen2.rmsnorm(x, params["final_norm"]["scale"], eps)
        w = params["head"]["w"]
        # every sequence has seq - 1 targets: the mean of the sequences'
        # means is the mean over the batch
        return jnp.mean(jax.lax.map(lambda r: sequence_loss(*r, w), (x, toks)))
    return loss
