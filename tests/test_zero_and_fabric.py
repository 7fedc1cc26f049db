"""ZeRO layouts, migration plans, snapshot, live remap — unit + property."""
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:   # container lacks hypothesis -> deterministic stub
    from _hypothesis_stub import given, settings, strategies as st

from repro.core import zero
from repro.core.fabric.remap import IntegrityError, LiveRemap
from repro.core.fabric.snapshot import SnapshotPool
from repro.optim.adam import (AdamConfig, adam_update_flat,
                              adam_update_flat_np)


# -------------------------------------------------------------- zero layout --
class TestLayouts:
    @given(st.lists(st.integers(8, 200), min_size=1, max_size=6),
           st.integers(1, 8), st.sampled_from(["contiguous", "interleaved"]))
    @settings(max_examples=80, deadline=None)
    def test_partition_exact(self, sizes, dp, kind):
        lay = zero.Layout(kind, tuple(sizes), dp)
        covered = []
        for j in range(dp):
            covered += lay.owner_intervals(j)
        covered.sort()
        # exact disjoint cover of [0, total)
        cur = 0
        for s, e in covered:
            assert s == cur
            cur = e
        assert cur == lay.total

    def test_interleaved_same_rank_owns_every_layer(self):
        lay = zero.Layout("interleaved", (40, 80, 120), 4)
        ivs = lay.owner_intervals(2)
        assert len(ivs) == 3      # one shard per layer


class TestMigrationPlan:
    @given(st.lists(st.integers(64, 512), min_size=2, max_size=5),
           st.integers(2, 6))
    @settings(max_examples=60, deadline=None)
    def test_interleaved_is_pure_p2p(self, sizes, dp):
        pos = len(sizes) // 2
        plan = zero.migration_plan("interleaved", sizes, pos, dp, 0, 1, sizes[:1])
        assert all(not t.intra_stage for t in plan)
        assert len(plan) == dp
        assert sum(t.nbytes for t in plan) == sizes[pos]
        # disjoint rank-to-rank: src == dst index
        assert all(t.src_rank == t.dst_rank for t in plan)

    @given(st.lists(st.integers(64, 512), min_size=2, max_size=5),
           st.integers(2, 6))
    @settings(max_examples=60, deadline=None)
    def test_contiguous_costs_more(self, sizes, dp):
        pos = len(sizes) // 2
        plan_c = zero.migration_plan("contiguous", sizes, pos, dp, 0, 1, sizes[:1])
        b = zero.plan_bytes(plan_c)
        # cross-stage bytes = the migrating layer exactly
        assert b["cross_stage"] == sizes[pos]
        # intra-stage resharding appears for dp > 1 (unless cuts align)
        theo = zero.theoretical_bytes("contiguous", sizes[pos], dp)
        inter = zero.theoretical_bytes("interleaved", sizes[pos], dp)
        assert inter == sizes[pos]
        assert b["total"] >= inter  # contiguous never cheaper
        # theoretical closed form is an upper-bound-ish estimate
        assert b["total"] <= theo * 2.5 + 64


# ---------------------------------------------------------------- snapshot --
class TestSnapshot:
    def test_ring_identity_after_steps(self):
        """Host snapshot == neighbor device state after every step."""
        import jax.numpy as jnp
        n, m = 4, 64
        rng = np.random.default_rng(0)
        adam = AdamConfig()
        states = [{"master": rng.normal(size=m).astype(np.float32),
                   "mu": np.zeros(m, np.float32), "nu": np.zeros(m, np.float32)}
                  for _ in range(n)]
        pool = SnapshotPool(n, adam)
        pool.bootstrap(0, states)
        for step in range(1, 4):
            grads = [rng.normal(size=m).astype(np.float32) for _ in range(n)]
            # device updates
            for j in range(n):
                _, new = adam_update_flat(jnp.asarray(grads[j]),
                                          {k: jnp.asarray(v) for k, v in states[j].items()},
                                          step, adam)
                states[j] = {k: np.asarray(v) for k, v in new.items()}
            pool.snapshot_step(step, grads, step)
            for i in range(n):
                j = pool.backup_rank(i)
                for comp in ("master", "mu", "nu"):
                    np.testing.assert_array_equal(pool.host[i][comp],
                                                  states[j][comp])

    def test_grad_bytes_4x_smaller(self):
        pool = SnapshotPool(2, AdamConfig())
        pool.bootstrap(0, [{"master": np.zeros(10, np.float32),
                            "mu": np.zeros(10, np.float32),
                            "nu": np.zeros(10, np.float32)}] * 2)
        st_ = pool.snapshot_step(1, [np.zeros(10, np.float32)] * 2, 1)
        assert st_.state_bytes_equiv >= 3 * st_.grad_bytes_sent

    def test_bf16_compression_halves_bytes_bounded_drift(self):
        import jax.numpy as jnp
        rng = np.random.default_rng(1)
        n, m = 2, 256
        states = [{"master": rng.normal(size=m).astype(np.float32),
                   "mu": np.zeros(m, np.float32), "nu": np.zeros(m, np.float32)}
                  for _ in range(n)]
        exact = SnapshotPool(n, AdamConfig())
        comp = SnapshotPool(n, AdamConfig(), compress="bf16")
        exact.bootstrap(0, states)
        comp.bootstrap(0, states)
        grads = [rng.normal(size=m).astype(np.float32) for _ in range(n)]
        s1 = exact.snapshot_step(1, grads, 1)
        s2 = comp.snapshot_step(1, grads, 1)
        assert s2.grad_bytes_sent * 2 == s1.grad_bytes_sent
        # drift bounded by bf16 rounding through one Adam step
        for i in range(n):
            d = np.abs(exact.host[i]["master"] - comp.host[i]["master"]).max()
            assert d < 1e-4, d


# -------------------------------------------------------------- live remap --
class TestLiveRemap:
    def _setup(self, total, dp, kind):
        lay = zero.Layout(kind, (total,), dp) if kind == "contiguous" else \
            zero.Layout(kind, (total // 2, total - total // 2), dp)
        return lay

    @given(st.integers(2, 6), st.integers(0, 5),
           st.sampled_from(["contiguous", "interleaved"]))
    @settings(max_examples=60, deadline=None)
    def test_shrink_preserves_state(self, dp, fail_idx, kind):
        if fail_idx >= dp or dp < 2:
            return
        sizes = (96, 160)
        lay = zero.Layout(kind, sizes, dp)
        total = lay.total
        truth = np.arange(total, dtype=np.float32)
        surviving = [r for r in range(dp) if r != fail_idx]
        device_parts = {r: lay.owner_intervals(r) for r in surviving}
        host_parts = {fail_idx: lay.owner_intervals(fail_idx)}
        new_lay = zero.Layout(kind, sizes, dp - 1)
        target = {r: new_lay.owner_intervals(j) for j, r in enumerate(surviving)}
        rm = LiveRemap()
        plan = rm.compute_plan(total, device_parts, host_parts, target)
        # every target byte covered exactly once
        m = plan.overlap_matrix(dp)
        assert m.sum() == total

        def segs_for(parts):
            return {r: { (s, e): truth[s:e] for (s, e) in ivs }
                    for r, ivs in parts.items()}

        out = rm.execute(plan, total, segs_for(device_parts), segs_for(host_parts))
        # reassemble and compare
        rebuilt = np.zeros(total, np.float32)
        for j, r in enumerate(surviving):
            off = 0
            shard = out[r]
            for s, e in new_lay.owner_intervals(j):
                rebuilt[s:e] = shard[off:off + (e - s)]
                off += e - s
        np.testing.assert_array_equal(rebuilt, truth)

    def test_integrity_failure_detected(self):
        rm = LiveRemap()
        with pytest.raises(IntegrityError):
            rm.integrity_check(100, {0: [(0, 40)]}, {1: [(50, 100)]})


# ------------------------------------------------------------- host adam --
@pytest.mark.parametrize("step", [1, 7])
def test_host_adam_bit_identical_to_eager(step):
    """The in-place numpy AdamW keeps the eager op sequence: same bits."""
    import jax.numpy as jnp
    rng = np.random.default_rng(step)
    n = 4097
    g = rng.standard_normal(n).astype(np.float32)
    state = {"master": rng.standard_normal(n).astype(np.float32),
             "mu": (rng.standard_normal(n) * 0.01).astype(np.float32),
             "nu": np.abs(rng.standard_normal(n) * 0.01).astype(np.float32)}
    before = {k: v.copy() for k, v in state.items()}
    got = adam_update_flat_np(g, state, step, AdamConfig())
    _, want = adam_update_flat(jnp.asarray(g),
                               {k: jnp.asarray(v) for k, v in state.items()},
                               step, AdamConfig())
    for k in want:
        np.testing.assert_array_equal(got[k], np.asarray(want[k]))
        np.testing.assert_array_equal(state[k], before[k])   # input intact


#: the blocked update at test scale: blocks of 64 elements, threads from 256
SMALL_BLOCK, SMALL_THRESHOLD, SMALL_THREADS = 64, 256, 3


@pytest.fixture
def small_blocks(monkeypatch):
    from repro.optim import adam
    monkeypatch.setattr(adam, "BLOCK", SMALL_BLOCK)
    monkeypatch.setattr(adam, "THRESHOLD", SMALL_THRESHOLD)
    monkeypatch.setattr(adam, "THREADS", SMALL_THREADS)
    monkeypatch.setattr(adam, "_pool", None)
    yield adam
    if adam._pool is not None:
        adam._pool.shutdown()


def _adam_case(n, step):
    rng = np.random.default_rng(n * 10 + step)
    g = rng.standard_normal(n).astype(np.float32)
    state = {"master": rng.standard_normal(n).astype(np.float32),
             "mu": (rng.standard_normal(n) * 0.01).astype(np.float32),
             "nu": np.abs(rng.standard_normal(n) * 0.01).astype(np.float32)}
    return g, state


@pytest.mark.parametrize("in_place", [False, True], ids=["fresh", "in_place"])
@pytest.mark.parametrize("step", [1, 7])
@pytest.mark.parametrize("n, blocks, threads", [
    (0, 1, 1),                                   # empty
    (SMALL_THRESHOLD - 56, 1, 1),                # below the threshold
    (8 * SMALL_BLOCK, 8, SMALL_THREADS),         # a multiple of the block
    (8 * SMALL_BLOCK - 1, 8, SMALL_THREADS),
    (8 * SMALL_BLOCK + 1, 9, SMALL_THREADS),
    (13 * SMALL_BLOCK + 5, 14, SMALL_THREADS),   # several blocks a thread
])
def test_blocked_host_adam_bit_identical_to_eager(small_blocks, n, blocks,
                                                  threads, step, in_place):
    """Blocks and threads change no bit of any component; in place (``out``
    the state itself) gives the same bits, and without ``out`` the input
    stays intact."""
    import jax.numpy as jnp
    adam = small_blocks
    assert adam.adam_plan(n) == {"blocks": blocks, "threads": threads}
    g, state = _adam_case(n, step)
    before = {k: v.copy() for k, v in state.items()}
    _, want = adam_update_flat(jnp.asarray(g),
                               {k: jnp.asarray(v) for k, v in state.items()},
                               step, AdamConfig())
    got = adam_update_flat_np(g, state, step, AdamConfig(),
                              out=state if in_place else None)
    for k in want:
        np.testing.assert_array_equal(got[k], np.asarray(want[k]))
        if in_place:
            assert got[k] is state[k]
        else:
            np.testing.assert_array_equal(state[k], before[k])


def test_host_adam_sweep_is_bit_identical(small_blocks, capsys):
    """``benchmarks/host_adam.py``, the sweep that sets ``BLOCK`` and
    ``THREADS``, runs every setting bit-identical to one block."""
    from benchmarks import host_adam
    host_adam.main(n=5 * SMALL_BLOCK + 3)
    rows = [r for r in capsys.readouterr().out.splitlines()
            if r.startswith("host_adam[")]
    assert len(rows) > 2 * len(host_adam.BLOCKS)
    assert all(r.endswith("bit_identical=True") for r in rows[1:])


def test_in_place_snapshot_keeps_its_views(small_blocks):
    """After in-place ``snapshot_step``s on the threaded path, ``host[i]``
    are still views of the pool's buffers and hold the neighbour's updated
    state; the write-time checksums hold, and a corruption after the step is
    still caught."""
    import jax.numpy as jnp
    n, m = 3, 5 * SMALL_BLOCK + 7     # 3 x 327 elements: blocks over threads
    assert small_blocks.adam_plan(n * m)["threads"] > 1
    rng = np.random.default_rng(3)
    adam = AdamConfig()
    states = [{"master": rng.normal(size=m).astype(np.float32),
               "mu": np.zeros(m, np.float32), "nu": np.zeros(m, np.float32)}
              for _ in range(n)]
    pool = SnapshotPool(n, adam)
    pool.bootstrap(0, states)
    for step in range(1, 4):
        grads = [rng.normal(size=m).astype(np.float32) for _ in range(n)]
        for j in range(n):
            _, new = adam_update_flat(
                jnp.asarray(grads[j]),
                {k: jnp.asarray(v) for k, v in states[j].items()}, step, adam)
            states[j] = {k: np.asarray(v) for k, v in new.items()}
        cat = dict(pool._cat) if pool._cat is not None else None
        pool.snapshot_step(step, grads, step)
        if cat is not None:       # the step wrote into the same buffers
            assert all(pool._cat[c] is cat[c] for c in cat)
        for i in range(n):
            for comp in ("master", "mu", "nu"):
                view = pool.host[i][comp]
                assert view.base is pool._cat[comp]
                np.testing.assert_array_equal(
                    view, states[pool.backup_rank(i)][comp])
    assert all(pool.verify_shard(j) for j in range(n))
    pool.corrupt_shard(1, "mu", index=11)
    assert not pool.verify_shard(1)
    assert pool.verify_shard(0) and pool.verify_shard(2)
