"""Elastic events move state without changing it: a ZeRO-layout twin.

The interleaved and contiguous ZeRO partitions are pure placement choices.
Two clusters that differ only in ``zero_layout`` run the one grad program on
the same inputs, and their AdamW (stage and snapshot) is elementwise, so it
gives the same bits in any shard order; only the remap, widen and migration
move different intervals between ranks.  So a full elastic run (train ->
fail-stop -> recover -> train -> rejoin -> train) must give the same losses,
the same model-order master/mu/nu of every stage after every event, and the
same model, bit for bit, under both layouts.  This is the contract
``core.invariants.ParameterConsistencyChecker`` enforces on every fuzzed
trace.
"""
from types import SimpleNamespace

import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from repro.core.cluster import VirtualCluster
from repro.core.events import ElasticEvent, EventKind
from repro.core.invariants import ParameterConsistencyChecker
from repro.core.statespace import COMPONENTS
from repro.models import registry as R
from repro.scenarios import ClusterWorkload

# every test here drives real jit-compiled training on TWO clusters — the
# whole module lives in the slow shard (fast CI runs -m "not slow")
pytestmark = pytest.mark.slow

CFG = R.tiny_config("dense", num_layers=8, dropout_rate=0.1)
LAYOUTS = ("interleaved", "contiguous")


def mk(zero_layout="interleaved", dp=4, pp=2, **kw):
    return VirtualCluster(CFG, dp=dp, pp=pp, global_batch=16, num_micro=2,
                          seq_len=16, seed=0, zero_layout=zero_layout, **kw)


def state(cl: VirtualCluster):
    """Structure and model-order state of every stage (copies)."""
    return [(list(st.dp_ranks), list(st.entries), list(st.sizes),
             {c: st.full(c).copy() for c in COMPONENTS})
            for st in cl.stages]


def assert_same_state(a, b, where=""):
    assert len(a) == len(b)
    for p, ((ra, ea, za, fa), (rb, eb, zb, fb)) in enumerate(zip(a, b)):
        assert (ra, ea, za) == (rb, eb, zb), f"{where} stage {p} structure"
        for c in COMPONENTS:
            np.testing.assert_array_equal(
                fa[c], fb[c], err_msg=f"{where} stage {p} component {c}")


def trajectory(cl: VirtualCluster, fail=(1, 1), steps=(3, 3, 2)):
    """Train, fail-stop ``fail``, train, rejoin it, train.  Returns the
    losses, the two recovery records, and the state after each phase."""
    losses = cl.run(steps[0])
    states = [state(cl)]
    rec1 = cl.recover_fail_stop(*fail)
    states.append(state(cl))
    losses += cl.run(steps[1])
    states.append(state(cl))
    rec2 = cl.recover_scale_out(*fail)
    states.append(state(cl))
    losses += cl.run(steps[2])
    states.append(state(cl))
    return cl, losses, (rec1, rec2), states


def assert_twins_agree(ta, tb):
    (_, la, _, sa), (_, lb, _, sb) = ta, tb
    assert la == lb
    for k, (a, b) in enumerate(zip(sa, sb)):
        assert_same_state(a, b, f"phase {k}")


class TestElasticTrajectoryBitIdentical:
    """Dense, dropout on (RNG resharding exercised); the fail-stop leaves
    uneven micro-batches (16/2/3 ranks -> sizes [3,3,2]), so the bucketed
    grad path runs too."""

    @pytest.fixture(scope="class")
    def trajectories(self):
        return {layout: trajectory(mk(layout)) for layout in LAYOUTS}

    def test_losses_bit_identical(self, trajectories):
        a, b = (trajectories[k][1] for k in LAYOUTS)
        assert len(a) == len(b) == 8
        assert a == b

    def test_stage_state_bit_identical_after_each_event(self, trajectories):
        a, b = (trajectories[k] for k in LAYOUTS)
        assert a[0].per_rank_mbs == b[0].per_rank_mbs
        assert_twins_agree(a, b)
        # the layouts really differ: some shard is not the same vector
        sa, sb = a[0].stages[0], b[0].stages[0]
        r = sa.dp_ranks[0]
        assert not np.array_equal(sa.shard(r)["master"],
                                  sb.shard(r)["master"])

    def test_params_bit_identical(self, trajectories):
        a, b = (trajectories[k][0] for k in LAYOUTS)
        va = np.asarray(ravel_pytree((a.stem, a.layer_params, a.head))[0])
        vb = np.asarray(ravel_pytree((b.stem, b.layer_params, b.head))[0])
        np.testing.assert_array_equal(va, vb)

    def test_mttr_records_identical(self, trajectories):
        """The deterministic record fields agree (``plan`` is measured
        planner wall clock, ``remap`` prices layout-specific intervals)."""
        ra, rb = (trajectories[k][2] for k in LAYOUTS)
        for a, b in zip(ra, rb):
            assert set(a) == set(b)
            for k in ("detect", "communicator", "rng_moves"):
                assert a[k] == b[k], k


class TestOtherModesBitIdentical:
    def test_naive_rng_mode(self):
        """Rank-addressed streams move with the dataflow, the same way under
        both layouts."""
        assert_twins_agree(*(trajectory(mk(layout, rng_mode="naive"))
                             for layout in LAYOUTS))

    def test_checker_from_contiguous(self):
        """The same trajectory from a contiguous cluster, through the checker
        itself: its twin is the interleaved cluster, and every event and
        step passes at bit equality."""
        workload = ClusterWorkload()
        runner = SimpleNamespace(workload=workload)
        cl = workload.make_cluster(zero_layout="contiguous")
        chk = ParameterConsistencyChecker()
        chk.on_cluster_start(runner, cl)
        assert chk.twin.zero_layout == "interleaved"
        rank = (workload.rank(1, 1),)
        events = {3: ElasticEvent(EventKind.FAIL_STOP, 3, rank),
                  6: ElasticEvent(EventKind.SCALE_OUT, 6, rank)}
        for step in range(8):
            if step in events:
                ev = events[step]
                chk.after_cluster_event(step, ev, cl, cl.apply_event(ev))
            chk.after_cluster_step(step, cl, cl.train_step())
        assert cl.losses == chk.twin.losses and len(cl.losses) == 8
        assert len(cl.recoveries) == 2

    @pytest.mark.parametrize("family", ["moe", "ssm"])
    def test_families(self, family):
        """Across block types (MoE routing, SSD recurrences), through a
        fail-stop and a rejoin."""
        cfg = R.tiny_config(family, dropout_rate=0.1) if family != "moe" \
            else R.tiny_config(family, dropout_rate=0.1, capacity_factor=16.0)
        runs = [trajectory(VirtualCluster(cfg, dp=2, pp=2, global_batch=8,
                                          num_micro=2, seq_len=16, seed=0,
                                          zero_layout=layout),
                           fail=(1, 0), steps=(2, 1, 1))
                for layout in LAYOUTS]
        assert_twins_agree(*runs)


class TestRecoveryRecordSchema:
    """All recovery records share ONE schema (fail-slow / scale-out / DVFS
    included), so ``_merge_recovery_records`` output shape never depends on
    the event kind."""

    def test_all_kinds_share_schema(self):
        from repro.core.events import ElasticEvent, EventKind
        cl = mk()
        cl.run(1)
        recs = {
            "fail_stop": cl.recover_fail_stop(0, 0),
            "fail_slow": cl.recover_fail_slow(1, 1, 1.5),
            "scale_out": cl.recover_scale_out(0, 0),
            "dvfs": cl.apply_event(ElasticEvent(
                EventKind.DVFS_SET, cl.step_count, (3,), freq=1.2)),
        }
        keysets = {k: frozenset(v) for k, v in recs.items()}
        assert len(set(keysets.values())) == 1, keysets
        assert all("rng_moves" in v for v in recs.values())
        # merged burst records keep the same shape
        from repro.core.cluster import _merge_recovery_records
        merged = _merge_recovery_records([recs["fail_stop"],
                                          recs["fail_slow"]])
        assert set(merged) == set(recs["fail_stop"])
