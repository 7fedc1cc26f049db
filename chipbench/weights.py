"""The benchmark's own weights: one jitted call from the seed, on the device,
in each leaf's own dtype.

A norm's ``scale`` is ones, an ``embedding`` is standard normal, and every
other matrix is normal with standard deviation fan_in ** -0.5 (fan_in is
the second-to-last axis, so a leaf stacked over layers keeps its scale).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.common import leaf_name


def _leaf(key, name: str, sd):
    if name.endswith("scale"):
        return jnp.ones(sd.shape, sd.dtype)
    scale = 1.0 if name.endswith("embedding") else sd.shape[-2] ** -0.5
    return (jax.random.normal(key, sd.shape, jnp.float32) * scale
            ).astype(sd.dtype)


def make(shapes, seed: int):
    """Weights of the ``ShapeDtypeStruct`` tree ``shapes`` from ``seed``."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [leaf_name(p) for p, _ in paths]
    sds = [s for _, s in paths]

    @jax.jit
    def build(key):
        leaves = [_leaf(jax.random.fold_in(key, i), n, s)
                  for i, (n, s) in enumerate(zip(names, sds))]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return build(jax.random.key(seed))
