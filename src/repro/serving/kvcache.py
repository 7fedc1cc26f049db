"""Slot-indexed KV-cache pools: explicit pytrees with gather/scatter moves.

Each serving replica owns one :class:`KVPool` — the stacked-cache pytree of
``models.registry.serving_hooks(cfg).init_caches(n_slots, max_len)`` plus
per-slot occupancy metadata.  Every cache leaf carries the slot dimension on
axis 1 (axis 0 is the layer/repeats stacking axis), and per-request extras
(e.g. an enc-dec encoder output) carry it on axis 0.

Gather/scatter follow the flat-state backbone's idiom
(``core/statespace.py``): one fancy-index per leaf instead of per-slot Python
loops.  A migration between replicas is ``gather_slots`` on the source pool +
``scatter_slots`` into the destination pool — a pure array copy, so migrated
decode streams are bit-identical to undisturbed ones (the serving analogue of
the training step's zero-copy shard views being bit-exact).
"""
from __future__ import annotations

from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

SLOT_AXIS = 1        # stacked caches: [repeats/layers, slot, ...]
EXTRAS_AXIS = 0      # per-slot extras:  [slot, ...]


def _ix(ids: Sequence[int], axis: int) -> Tuple:
    """A single fancy-index selecting ``ids`` along ``axis``."""
    return tuple([slice(None)] * axis + [np.asarray(ids, dtype=np.int32)])


def gather_slots(tree, ids: Sequence[int], axis: int = SLOT_AXIS):
    """Slice ``ids`` out of every leaf along the slot axis (one fancy-index
    per leaf, mirroring ``IntervalTable.gather``)."""
    import jax
    idx = _ix(ids, axis)
    return jax.tree.map(lambda a: a[idx], tree)


def scatter_slots(dst, src, ids: Sequence[int], axis: int = SLOT_AXIS):
    """Write ``src`` (a gathered slice) into ``dst`` at ``ids``."""
    import jax
    idx = _ix(ids, axis)
    return jax.tree.map(
        lambda d, s: d.at[idx].set(s.astype(d.dtype)), dst, src)


def tree_nbytes(tree) -> int:
    import jax
    return int(sum(np.prod(l.shape) * np.dtype(l.dtype).itemsize
                   for l in jax.tree.leaves(tree)))


def slot_kv_bytes(cfg, max_len: int, init_caches=None) -> int:
    """Per-slot KV bytes for migration accounting, from cache *shapes* only
    (``jax.eval_shape`` — nothing is allocated)."""
    import jax
    if init_caches is None:
        from repro.models import registry as R
        init_caches = R.serving_hooks(cfg).init_caches
    shapes = jax.eval_shape(lambda: init_caches(1, max_len))
    return tree_nbytes(shapes)


class KVPool:
    """Per-replica slot bookkeeping over one stacked cache pytree.

    ``caches=None`` puts the pool in synthetic mode (scheduler/latency runs
    at trace scale): occupancy and byte accounting behave identically but no
    arrays are moved.
    """

    def __init__(self, n_slots: int, caches=None, *, slot_bytes: int = 0):
        self.n_slots = int(n_slots)
        self.caches = caches
        self.extras = None                 # lazily shaped from first template
        self.slot_req = np.full(self.n_slots, -1, dtype=np.int64)
        self.lengths = np.zeros(self.n_slots, dtype=np.int64)
        self._slot_bytes = int(slot_bytes) if slot_bytes else (
            tree_nbytes(caches) // max(self.n_slots, 1) if caches is not None
            else 0)

    # -- occupancy ---------------------------------------------------------
    def free_slots(self) -> List[int]:
        return [int(s) for s in np.flatnonzero(self.slot_req < 0)]

    def active_slots(self) -> List[int]:
        return [int(s) for s in np.flatnonzero(self.slot_req >= 0)]

    @property
    def n_free(self) -> int:
        return int((self.slot_req < 0).sum())

    @property
    def n_active(self) -> int:
        return self.n_slots - self.n_free

    def assign(self, slot: int, rid: int, length: int = 0):
        assert self.slot_req[slot] < 0, f"slot {slot} occupied"
        self.slot_req[slot] = rid
        self.lengths[slot] = length

    def release(self, slot: int):
        self.slot_req[slot] = -1
        self.lengths[slot] = 0

    def slot_bytes(self, slot: int) -> int:
        del slot  # uniform slots (max_len-sized); kept for API symmetry
        return self._slot_bytes

    # -- array movement ----------------------------------------------------
    def ensure_extras(self, template_slice):
        """Allocate the per-slot extras pytree from a [1, ...] template."""
        import jax
        import jax.numpy as jnp
        if self.extras is None and template_slice is not None:
            self.extras = jax.tree.map(
                lambda a: jnp.zeros((self.n_slots,) + tuple(a.shape[1:]),
                                    a.dtype), template_slice)

    def write(self, slot: int, cache_slice, extra_slice=None):
        """Scatter a single gathered slice ([.., 1, ..]) into ``slot``."""
        if self.caches is not None and cache_slice is not None:
            self.caches = scatter_slots(self.caches, cache_slice, [slot])
        if extra_slice is not None:
            self.ensure_extras(extra_slice)
            self.extras = scatter_slots(self.extras, extra_slice, [slot],
                                        axis=EXTRAS_AXIS)

    def read(self, slot: int):
        """Gather one slot's (cache, extras) slices (shapes keep the slot
        dim, so they scatter straight into another pool)."""
        c = (gather_slots(self.caches, [slot]) if self.caches is not None
             else None)
        e = (gather_slots(self.extras, [slot], axis=EXTRAS_AXIS)
             if self.extras is not None else None)
        return c, e


def migrate_slot(src: KVPool, src_slot: int, dst: KVPool, dst_slot: int,
                 rid: int) -> int:
    """Move one in-flight slot between replicas; returns bytes moved.
    Pure gather+scatter — the migrated stream's continuation is bit-identical
    (tested by ``tests/test_serving.py``)."""
    c, e = src.read(src_slot)
    length = int(src.lengths[src_slot])
    dst.assign(dst_slot, rid, length)
    dst.write(dst_slot, c, e)
    src.release(src_slot)
    return src.slot_bytes(src_slot)
