"""Sharding rules for the production pjit path.

Mesh axes:
  single-pod: ("data", "model") = (16, 16)
  multi-pod : ("pod", "data", "model") = (2, 16, 16)

Policy (MaxText-style FSDP + TP, adapted per family):
  * batch                -> ("pod","data")          [DP]
  * weight in-dim  (d)   -> ("pod","data")          [ZeRO-3 / FSDP shard]
  * weight out-dim (ff/heads/vocab) -> "model"      [TP]
  * MoE expert dim       -> "model"                 [EP]
  * KV cache: batch -> DP axes; heads -> "model" if divisible, else seq -> "model"
  * every rule degrades to None if the dim is not divisible by the axis group
    (e.g. vocab 50280 or 51865 cannot shard over 16).

All functions are divisibility-safe so every (arch x shape x mesh) cell lowers.
"""
from __future__ import annotations

import math
from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig


def mesh_axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return math.prod(mesh.shape[a] for a in axes)


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _fit(mesh: Mesh, size: int, axes) -> Optional[Any]:
    """Return `axes` if `size` divides evenly over them, trying suffixes of
    the axis tuple before giving up (e.g. ("pod","data") -> ("data",))."""
    if axes is None:
        return None
    if isinstance(axes, str):
        axes = (axes,)
    for start in range(len(axes)):
        cand = axes[start:]
        if size % mesh_axis_size(mesh, cand) == 0:
            return cand if len(cand) > 1 else cand[0]
    return None


def _spec(mesh: Mesh, shape, *dim_axes) -> P:
    """Build a PartitionSpec fitting each dim; drop axes that don't divide."""
    assert len(shape) == len(dim_axes), (shape, dim_axes)
    used = set()
    entries = []
    for size, axes in zip(shape, dim_axes):
        fitted = _fit(mesh, size, axes)
        # an axis name may appear at most once in a PartitionSpec
        if fitted is not None:
            names = (fitted,) if isinstance(fitted, str) else tuple(fitted)
            if any(n in used for n in names):
                fitted = None
            else:
                used.update(names)
        entries.append(fitted)
    return P(*entries)


# --------------------------------------------------------------------------
# Parameter shardings
# --------------------------------------------------------------------------
def param_pspecs(cfg: ModelConfig, mesh: Mesh, params_shapes) -> Any:
    """Map a params shape-pytree -> PartitionSpec pytree by path rules."""
    DP = dp_axes(mesh)

    def rule(path, leaf):
        shape = tuple(leaf.shape)
        keys = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
        name = keys[-1] if keys else ""
        joined = "/".join(str(k) for k in keys)
        # strip leading stacked-repeats axis for block params under segments/
        stacked = ("segments" in joined) or ("encoder/" in joined and len(shape) >= 2) \
            or ("decoder/" in joined)
        core = shape[1:] if stacked and len(shape) >= 2 else shape
        lead = (None,) if stacked and len(shape) >= 2 else ()

        def out(*axes):
            sp = _spec(mesh, core, *axes)
            return P(*(lead + tuple(sp)))

        if name == "embedding":
            return out("model", DP)
        if name in ("wq", "wk", "wv", "wq_a", "wq_b", "wkv_a", "wkv_b",
                    "wg", "wu", "wi", "in_proj"):
            if len(core) == 3:           # MoE expert weights [E, d, ff]
                return out("model", DP, None)
            return out(DP, "model")
        if name in ("wo", "out_proj"):
            if len(core) == 3:           # MoE [E, ff, d]
                return out("model", None, DP)
            return out("model", DP)
        if name in ("bq", "bk", "bv"):   # q/k/v bias: as wq/wk/wv's out-dim
            return out("model")
        if name == "w":                  # lm head [d, V]
            return out(DP, "model")
        if name == "router":
            return out(DP, None)
        if name == "conv_w":
            return out(None, "model")
        if name == "enc_pos":
            return out(None, DP)
        # scale / A_log / D / dt_bias / other small vectors: replicate
        return P(*(lead + (None,) * len(core)))

    return jax.tree_util.tree_map_with_path(rule, params_shapes)


def batch_pspecs(cfg: ModelConfig, mesh: Mesh, batch_shapes) -> Any:
    DP = dp_axes(mesh)

    def rule(path, leaf):
        shape = tuple(leaf.shape)
        sp = [None] * len(shape)
        fitted = _fit(mesh, shape[0], DP)
        sp[0] = fitted
        return P(*sp)

    return jax.tree_util.tree_map_with_path(rule, batch_shapes)


def cache_pspecs(cfg: ModelConfig, mesh: Mesh, cache_shapes) -> Any:
    """KV caches [rep, B, T, Hkv, hd] / MLA [rep, B, T, r] /
    mamba ssm [rep, B, h, p, n], conv [rep, B, k-1, c]."""
    DP = dp_axes(mesh)

    def rule(path, leaf):
        shape = tuple(leaf.shape)
        keys = "/".join(str(getattr(p, "key", getattr(p, "name", p))) for p in path)
        # leading repeats axis
        if len(shape) == 5 and "ssm" not in keys:      # [rep,B,T,H,hd]
            rep, B, T, H, hd = shape
            h_fit = _fit(mesh, H, "model")
            if h_fit is not None:
                return _spec(mesh, shape, None, DP, None, "model", None)
            return _spec(mesh, shape, None, DP, "model", None, None)
        if len(shape) == 5:                            # mamba ssm [rep,B,h,p,n]
            return _spec(mesh, shape, None, DP, "model", None, None)
        if len(shape) == 4:                            # MLA latent / conv state
            # [rep,B,T,r] -> shard T over model when batch tiny
            return _spec(mesh, shape, None, DP, "model", None)
        return P(*([None] * len(shape)))

    return jax.tree_util.tree_map_with_path(rule, cache_shapes)


def to_shardings(mesh: Mesh, pspecs) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                        is_leaf=lambda x: isinstance(x, P))


def opt_pspecs(param_specs) -> Any:
    """Adam mu/nu/master share the param sharding; scalars replicated."""
    return param_specs
