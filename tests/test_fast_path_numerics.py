"""The flat-state fast path agrees with the seed implementation.

The numerics guardrail of the fast-path refactor: a full elastic run
(train -> fail-stop -> recover -> train -> rejoin -> train) produces the
same loss trajectory and post-recovery shard contents under
``fast_path=True`` (vmap-batched grads, fused host Adam, indexed scatter,
batched recovery) as under ``fast_path=False`` (the seed per-item /
per-shard / per-entry loops preserved in ``core/legacy.py``), within the
declared tolerance of ``core.invariants.ParameterConsistencyChecker``.
The two paths are different XLA programs, so under jax 0.9.0 they agree to
float32 round-off and not bit for bit; the test names keep their history.
Structure (ranks, entries, sizes, recovery records) stays exact.
"""
import numpy as np
import pytest

from repro.core.cluster import VirtualCluster
from repro.core.invariants import ParameterConsistencyChecker
from repro.core.statespace import COMPONENTS
from repro.models import registry as R

# every test here drives real jit-compiled training on TWO clusters — the
# whole module lives in the slow shard (fast CI runs -m "not slow")
pytestmark = pytest.mark.slow

CFG = R.tiny_config("dense", num_layers=8, dropout_rate=0.1)


def mk(fast, dp=4, pp=2, **kw):
    return VirtualCluster(CFG, dp=dp, pp=pp, global_batch=16, num_micro=2,
                          seq_len=16, seed=0, fast_path=fast, **kw)


def param_atol(cl: VirtualCluster) -> float:
    return ParameterConsistencyChecker().param_atol(cl)


def assert_losses_close(a, b):
    assert len(a) == len(b)
    np.testing.assert_allclose(a, b, rtol=ParameterConsistencyChecker.LOSS_RTOL,
                               atol=0.0)


def assert_state_identical(a: VirtualCluster, b: VirtualCluster):
    """Same structure exactly; same state within the declared tolerance."""
    assert len(a.stages) == len(b.stages)
    assert a.opt_step == b.opt_step
    atol = param_atol(a)
    for p, (sa, sb) in enumerate(zip(a.stages, b.stages)):
        assert sa.dp_ranks == sb.dp_ranks
        assert sa.entries == sb.entries and sa.sizes == sb.sizes
        for c in COMPONENTS:
            np.testing.assert_allclose(
                a._stage_full_vec(sa, c), b._stage_full_vec(sb, c),
                rtol=0.0, atol=atol, err_msg=f"stage {p} component {c}")
        # per-rank shard contents too (layout permutations must agree)
        for r in sa.dp_ranks:
            for c in COMPONENTS:
                np.testing.assert_allclose(
                    sa.shard(r)[c], sb.shard(r)[c], rtol=0.0, atol=atol,
                    err_msg=f"stage {p} rank {r} component {c}")


class TestElasticTrajectoryBitIdentical:
    """8+ steps with a fail-stop AND a scale-out on a tiny config; dropout
    on (RNG resharding exercised); uneven post-failure micro-batches
    (16/2/3 ranks -> sizes [3,3,2]) exercise the bucketed grad path."""

    @pytest.fixture(scope="class")
    def trajectories(self):
        out = {}
        for fast in (False, True):
            cl = mk(fast)
            losses = cl.run(3)
            rec1 = cl.recover_fail_stop(1, 1)
            losses += cl.run(3)
            rec2 = cl.recover_scale_out(1, 1)
            losses += cl.run(2)
            out[fast] = (cl, losses, rec1, rec2)
        return out

    def test_losses_bit_identical(self, trajectories):
        _, ref, _, _ = trajectories[False]
        _, fast, _, _ = trajectories[True]
        assert len(ref) == len(fast) == 8
        assert_losses_close(fast, ref)

    def test_post_recovery_shards_bit_identical(self, trajectories):
        assert_state_identical(trajectories[False][0], trajectories[True][0])

    def test_params_bit_identical(self, trajectories):
        from jax.flatten_util import ravel_pytree
        a, b = trajectories[False][0], trajectories[True][0]
        va = np.asarray(ravel_pytree((a.stem, a.layer_params, a.head))[0])
        vb = np.asarray(ravel_pytree((b.stem, b.layer_params, b.head))[0])
        np.testing.assert_allclose(va, vb, rtol=0.0, atol=param_atol(a))

    def test_mttr_records_identical(self, trajectories):
        """Deterministic record fields agree (``plan`` is measured planner
        wall clock, so only its presence is checked)."""
        _, _, r1a, r2a = trajectories[False]
        _, _, r1b, r2b = trajectories[True]
        for ka in ("detect", "rng_moves"):
            assert r1a[ka] == r1b[ka]
        assert set(r1a) == set(r1b) and set(r2a) == set(r2b)


class TestOtherModesBitIdentical:
    def test_naive_rng_mode(self):
        """The rank-addressed sids construction differs between paths —
        must still agree (a wrong stream would move the loss by far more
        than the tolerance)."""
        ref = mk(False, rng_mode="naive").run(2)
        fast = mk(True, rng_mode="naive").run(2)
        assert_losses_close(fast, ref)

    @pytest.mark.parametrize("layout", ["contiguous"])
    def test_contiguous_layout(self, layout):
        a, b = mk(False, zero_layout=layout), mk(True, zero_layout=layout)
        la = a.run(2)
        lb = b.run(2)
        a.recover_fail_stop(2, 0)
        b.recover_fail_stop(2, 0)
        la += a.run(1)
        lb += b.run(1)
        assert_losses_close(lb, la)
        assert_state_identical(a, b)

    @pytest.mark.parametrize("family", ["moe", "ssm"])
    def test_families(self, family):
        """vmap-batched grads agree with per-item grads across block types
        (MoE routing, SSD recurrences)."""
        cfg = R.tiny_config(family, dropout_rate=0.1) if family != "moe" \
            else R.tiny_config(family, dropout_rate=0.1, capacity_factor=16.0)
        losses = {}
        for fast in (False, True):
            cl = VirtualCluster(cfg, dp=2, pp=2, global_batch=8, num_micro=2,
                                seq_len=16, seed=0, fast_path=fast)
            losses[fast] = cl.run(2)
        assert_losses_close(losses[True], losses[False])


class TestRecoveryRecordSchema:
    """All recovery records share ONE schema (fail-slow / scale-out / DVFS
    included), so ``_merge_recovery_records`` output shape never depends on
    the event kind."""

    def test_all_kinds_share_schema(self):
        from repro.core.events import ElasticEvent, EventKind
        cl = mk(True)
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
