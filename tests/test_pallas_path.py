"""Pallas kernels in the training hot path: flag threading, per-family
pallas-vs-jnp forward-loss tolerance, the platform's default for the
kernels, and the tolerance-tier invariant stack over an elastic scenario
(fail-stop + scale-out).  Tiny configs throughout; interpret-mode numeric
cases beyond the dense smoke are marked ``slow``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cluster import VirtualCluster
from repro.core.events import ElasticEvent, EventKind
from repro.core.invariants import (KernelConsistencyChecker,
                                   default_cluster_checkers)
from repro.kernels import ops
from repro.models import registry as R
from repro.scenarios import (ClusterScenarioRunner, ClusterWorkload, Scenario,
                             make_pallas_case, run_case)

LOSS_RTOL = KernelConsistencyChecker.LOSS_RTOL
LOSS_ATOL = KernelConsistencyChecker.LOSS_ATOL


def _cfg(family):
    if family in ("moe", "hybrid"):
        # full capacity: no token dropping, so both modes route identically
        kw = {"capacity_factor": 16.0}
    else:
        kw = {}
    if family == "hybrid":
        kw["num_layers"] = 4       # block_pattern needs L % attn_period == 0
    return R.tiny_config(family, **kw)


def _batch(cfg, batch=2, seq=16):
    key = jax.random.key(7)
    toks = jax.random.randint(key, (batch, seq), 0, cfg.vocab_size)
    b = {"tokens": toks, "labels": toks}
    if cfg.is_encdec:
        b["frames"] = jax.random.normal(jax.random.fold_in(key, 1),
                                        (batch, seq, cfg.d_model))
    return b


class TestForwardLossTolerance:
    """make_train_loss(use_pallas=True) vs plain jnp, per family, within the
    KernelConsistencyChecker's loss tolerance."""

    @pytest.mark.parametrize("family", [
        "dense",
        pytest.param("moe", marks=pytest.mark.slow),
        pytest.param("ssm", marks=pytest.mark.slow),
        pytest.param("hybrid", marks=pytest.mark.slow),
        pytest.param("audio", marks=pytest.mark.slow),
    ])
    def test_loss_within_tier(self, family):
        cfg = _cfg(family)
        params = R.init_model(jax.random.key(0), cfg)
        b = _batch(cfg)
        l_jnp = float(R.make_train_loss(cfg, use_pallas=False)(params, b))
        l_pal = float(R.make_train_loss(cfg, use_pallas=True)(params, b))
        assert abs(l_pal - l_jnp) <= LOSS_ATOL + LOSS_RTOL * abs(l_jnp), \
            f"{family}: pallas loss {l_pal!r} vs jnp {l_jnp!r}"

    def test_grads_within_attention_tier(self):
        """The custom VJPs backpropagate the oracle's gradients; the only
        divergence source is the pallas forward activations, so grads stay
        within the (loosest) attention tier."""
        cfg = _cfg("dense")
        params = R.init_model(jax.random.key(0), cfg)
        b = _batch(cfg)
        g0 = jax.grad(R.make_train_loss(cfg, use_pallas=False))(params, b)
        g1 = jax.grad(R.make_train_loss(cfg, use_pallas=True))(params, b)
        tier = ops.TOLERANCE_TIERS["flash_attention"]
        for a, c in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            np.testing.assert_allclose(a, c, rtol=10 * tier["rtol"],
                                       atol=10 * tier["atol"])

    @pytest.mark.slow
    def test_encdec_remat_threads_with_pallas(self):
        """Satellite 1: make_train_loss forwards use_pallas AND remat to the
        enc-dec family (previously dropped on the floor).  Remat must not
        change the forward value in either kernel mode."""
        cfg = _cfg("audio")
        assert cfg.is_encdec
        params = R.init_model(jax.random.key(0), cfg)
        b = _batch(cfg)
        for up in (False, True):
            l0 = float(R.make_train_loss(cfg, use_pallas=up)(params, b))
            l1 = float(R.make_train_loss(cfg, use_pallas=up, remat=True)(
                params, b))
            assert l0 == l1, f"remat changed forward loss (use_pallas={up})"
            g = jax.grad(R.make_train_loss(cfg, use_pallas=up, remat=True))(
                params, b)
            assert all(bool(jnp.isfinite(x).all())
                       for x in jax.tree.leaves(g))


CLUSTER_KW = dict(dp=2, pp=1, global_batch=2, num_micro=1, seq_len=8, seed=0)


class TestUsePallasKnob:
    def test_env_and_arg_resolution(self):
        """``None`` takes the platform's answer (the kernels on a TPU, the
        plain-jnp forward elsewhere); an explicit argument wins."""
        cfg = R.tiny_config("dense", num_layers=2)
        on_tpu = jax.default_backend() == "tpu"
        assert VirtualCluster(cfg, **CLUSTER_KW).use_pallas is on_tpu
        for explicit in (False, True):
            assert VirtualCluster(cfg, use_pallas=explicit,
                                  **CLUSTER_KW).use_pallas is explicit

    def test_workload_field_reaches_cluster(self):
        w = ClusterWorkload(dp=2, pp=1, global_batch=2, num_micro=1,
                            seq_len=8, num_layers=2, use_pallas=True)
        assert w.make_cluster().use_pallas is True
        # the checker's twin flips the flag via the same override path
        assert w.make_cluster(use_pallas=False).use_pallas is False


class TestKernelConsistencyChecker:
    def test_default_checkers_swap(self):
        names = [c.name for c in default_cluster_checkers()]
        assert "parameter-consistency" in names
        assert "kernel-consistency" not in names
        names_p = [c.name for c in default_cluster_checkers(use_pallas=True)]
        assert "kernel-consistency" in names_p
        assert "parameter-consistency" not in names_p
        assert len(names) == len(names_p) == 4

    def test_pallas_elastic_scenario(self):
        """Acceptance: a fail-stop + scale-out scenario runs end-to-end in
        pallas mode under the four-invariant stack, with the jnp twin within
        the declared tolerance at every event and step boundary.  (Corpus
        spot-check skipped here for speed — tested directly in
        test_kernels.py.)"""
        w = ClusterWorkload(dp=2, pp=1, global_batch=2, num_micro=1,
                            seq_len=8, num_layers=2, use_pallas=True)
        sc = Scenario("pallas-elastic", (
            ElasticEvent(EventKind.FAIL_STOP, 1, (1,)),
            ElasticEvent(EventKind.SCALE_OUT, 2, (1,)),
        ), horizon=3)
        cks = default_cluster_checkers(use_pallas=True)
        cks[0].spot_check = False
        res = ClusterScenarioRunner(sc, w, checkers=cks).run()
        assert res is not None

    @pytest.mark.slow
    def test_pallas_elastic_scenario_full(self):
        """Fuller variant: pp=2, corpus spot-check on, run via the fuzz
        harness path (run_case picks the pallas checker stack from
        workload.use_pallas)."""
        from repro.scenarios import FuzzCase
        w = ClusterWorkload(dp=2, pp=2, global_batch=2, num_micro=1,
                            seq_len=8, num_layers=4, use_pallas=True)
        sc = Scenario("pallas-elastic-full", (
            ElasticEvent(EventKind.FAIL_STOP, 1, (1,)),
            ElasticEvent(EventKind.SCALE_OUT, 2, (1,)),
        ), horizon=3)
        run_case(FuzzCase(0, "pallas", sc, w))


class TestPallasFuzzMode:
    def test_case_shape(self):
        for seed in range(8):
            c = make_pallas_case(seed)
            assert c.mode == "pallas"
            assert c.workload.use_pallas is True
            assert c.workload.family in ("dense", "ssm")
            assert c.scenario.horizon <= 3
            assert "--mode pallas" in c.repro()
