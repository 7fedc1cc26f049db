"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.rmsnorm import rmsnorm_kernel
from repro.kernels.ssd_scan import ssd_scan_kernel

KEY = jax.random.key(42)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


class TestFlashAttention:
    @pytest.mark.parametrize("S", [64, 128, 256])
    @pytest.mark.parametrize("hd", [32, 64, 128])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_vs_oracle(self, S, hd, dtype):
        BH = 4
        q = jax.random.normal(jax.random.fold_in(KEY, 1), (BH, S, hd), dtype)
        k = jax.random.normal(jax.random.fold_in(KEY, 2), (BH, S, hd), dtype)
        v = jax.random.normal(jax.random.fold_in(KEY, 3), (BH, S, hd), dtype)
        o = flash_attention_kernel(q, k, v, causal=True, block_q=64, block_k=64,
                                   interpret=True)
        o_ref = ref.mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(o_ref, np.float32), **_tol(dtype))

    @pytest.mark.parametrize("blocks", [(32, 64), (64, 32), (128, 128)])
    def test_block_shapes(self, blocks):
        bq, bk = blocks
        S = 128
        q = jax.random.normal(jax.random.fold_in(KEY, 4), (2, S, 64))
        k = jax.random.normal(jax.random.fold_in(KEY, 5), (2, S, 64))
        v = jax.random.normal(jax.random.fold_in(KEY, 6), (2, S, 64))
        o = flash_attention_kernel(q, k, v, block_q=bq, block_k=bk,
                                   interpret=True)
        o_ref = ref.mha_reference(q, k, v)
        np.testing.assert_allclose(o, o_ref, rtol=2e-5, atol=2e-5)

    def test_noncausal(self):
        q = jax.random.normal(jax.random.fold_in(KEY, 7), (2, 128, 64))
        k = jax.random.normal(jax.random.fold_in(KEY, 8), (2, 128, 64))
        v = jax.random.normal(jax.random.fold_in(KEY, 9), (2, 128, 64))
        o = flash_attention_kernel(q, k, v, causal=False, block_q=64, block_k=64,
                                   interpret=True)
        np.testing.assert_allclose(o, ref.mha_reference(q, k, v, causal=False),
                                   rtol=2e-5, atol=2e-5)

    def test_gqa_wrapper(self):
        B, S, H, Hkv, hd = 2, 128, 8, 2, 64
        q = jax.random.normal(jax.random.fold_in(KEY, 10), (B, S, H, hd))
        k = jax.random.normal(jax.random.fold_in(KEY, 11), (B, S, Hkv, hd))
        v = jax.random.normal(jax.random.fold_in(KEY, 12), (B, S, Hkv, hd))
        o = ops.flash_attention(q, k, v)
        from repro.models.layers import _sdpa
        o_ref = _sdpa(q, k, v, causal=True)
        np.testing.assert_allclose(o, o_ref, rtol=1e-4, atol=1e-4)


class TestRmsnorm:
    @pytest.mark.parametrize("shape", [(4, 64), (2, 7, 96), (1, 1, 1, 128),
                                       (300, 256)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_vs_oracle(self, shape, dtype):
        x = jax.random.normal(jax.random.fold_in(KEY, 20), shape, dtype)
        s = jax.random.normal(jax.random.fold_in(KEY, 21), (shape[-1],))
        o = rmsnorm_kernel(x, s, interpret=True)
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(ref.rmsnorm_reference(x, s),
                                              np.float32), **_tol(dtype))


class TestSsdScan:
    @pytest.mark.parametrize("s,chunk", [(32, 8), (64, 16), (128, 32), (64, 64)])
    @pytest.mark.parametrize("dtype", [jnp.float32])
    def test_vs_sequential_oracle(self, s, chunk, dtype):
        b, h, p, n = 2, 4, 16, 8
        x = jax.random.normal(jax.random.fold_in(KEY, 30), (b, s, h, p), dtype) * 0.5
        dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 31), (b, s, h)))
        A = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 32), (h,)) * 0.3)
        Bm = jax.random.normal(jax.random.fold_in(KEY, 33), (b, s, h, n)) * 0.5
        Cm = jax.random.normal(jax.random.fold_in(KEY, 34), (b, s, h, n)) * 0.5
        y = ssd_scan_kernel(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
        y_ref, _ = ref.ssd_reference(x, dt, A, Bm, Cm)
        np.testing.assert_allclose(y, y_ref, rtol=5e-5, atol=5e-5)

    def test_groups_broadcast_via_ops(self):
        b, s, h, p, n, g = 2, 32, 4, 8, 8, 2
        x = jax.random.normal(jax.random.fold_in(KEY, 35), (b, s, h, p)) * 0.5
        dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 36), (b, s, h)))
        A = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 37), (h,)) * 0.3)
        Bm = jax.random.normal(jax.random.fold_in(KEY, 38), (b, s, g, n)) * 0.5
        Cm = jax.random.normal(jax.random.fold_in(KEY, 39), (b, s, g, n)) * 0.5
        y, _ = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=8)
        Bh = jnp.repeat(Bm, h // g, axis=2)
        Ch = jnp.repeat(Cm, h // g, axis=2)
        y_ref, _ = ref.ssd_reference(x, dt, A, Bh, Ch)
        np.testing.assert_allclose(y, y_ref, rtol=5e-5, atol=5e-5)

    def test_initial_state_raises(self):
        """The kernel always scans from zero state; a caller passing a resume
        state must get a crisp error, not silently-wrong results."""
        b, s, h, p, n = 1, 8, 2, 4, 4
        x = jnp.zeros((b, s, h, p))
        dt = jnp.ones((b, s, h))
        A = -jnp.ones((h,))
        Bm = jnp.zeros((b, s, h, n))
        Cm = jnp.zeros((b, s, h, n))
        state = jnp.zeros((b, h, p, n))
        with pytest.raises(ValueError, match="initial_state"):
            ops.ssd_scan(x, dt, A, Bm, Cm, chunk=8, initial_state=state)
        # also at trace time under an enclosing jit (Python-level check)
        with pytest.raises(ValueError, match="initial_state"):
            jax.jit(lambda *a: ops.ssd_scan(*a, chunk=8,
                                            initial_state=state))(
                x, dt, A, Bm, Cm)

    def test_group_divisibility_raises(self):
        b, s, h, p, n, g = 1, 8, 4, 4, 4, 3        # 4 % 3 != 0
        x = jnp.zeros((b, s, h, p))
        dt = jnp.ones((b, s, h))
        A = -jnp.ones((h,))
        Bm = jnp.zeros((b, s, g, n))
        Cm = jnp.zeros((b, s, g, n))
        with pytest.raises(ValueError, match="h=4.*g=3"):
            ops.ssd_scan(x, dt, A, Bm, Cm, chunk=8)

    def test_chunked_jnp_matches_oracle(self):
        """The model's jnp SSD path (mamba.ssd_chunked) == sequential oracle."""
        from repro.models.mamba import ssd_chunked
        b, s, h, p, n, g = 2, 64, 4, 8, 8, 1
        x = jax.random.normal(jax.random.fold_in(KEY, 40), (b, s, h, p)) * 0.5
        dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 41), (b, s, h)))
        A = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 42), (h,)) * 0.3)
        Bm = jax.random.normal(jax.random.fold_in(KEY, 43), (b, s, g, n)) * 0.5
        Cm = jax.random.normal(jax.random.fold_in(KEY, 44), (b, s, g, n)) * 0.5
        y, fin = ssd_chunked(x, dt, A, Bm, Cm, chunk=16)
        Bh = jnp.repeat(Bm, h, axis=2)
        Ch = jnp.repeat(Cm, h, axis=2)
        y_ref, fin_ref = ref.ssd_reference(x, dt, A, Bh, Ch)
        np.testing.assert_allclose(y, y_ref, rtol=5e-5, atol=5e-5)
        np.testing.assert_allclose(fin, fin_ref, rtol=5e-5, atol=5e-5)


class TestOpsWrappers:
    """The jitted public wrappers: GQA broadcast, non-default eps, errors."""

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("Hkv", [2, 8])
    def test_gqa_vs_ref(self, causal, Hkv):
        """Wrapper (GQA layout, Hkv <= H) == manual kv-repeat + oracle."""
        B, S, H, hd = 2, 64, 8, 32
        q = jax.random.normal(jax.random.fold_in(KEY, 50), (B, S, H, hd))
        k = jax.random.normal(jax.random.fold_in(KEY, 51), (B, S, Hkv, hd))
        v = jax.random.normal(jax.random.fold_in(KEY, 52), (B, S, Hkv, hd))
        o = ops.flash_attention(q, k, v, causal=causal)
        rep = H // Hkv
        kf = jnp.repeat(k, rep, axis=2).transpose(0, 2, 1, 3).reshape(B * H, S, hd)
        vf = jnp.repeat(v, rep, axis=2).transpose(0, 2, 1, 3).reshape(B * H, S, hd)
        qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
        o_ref = ref.mha_reference(qf, kf, vf, causal=causal)
        o_ref = o_ref.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
        tier = ops.TOLERANCE_TIERS["flash_attention"]
        np.testing.assert_allclose(o, o_ref, **tier)

    def test_head_divisibility_raises(self):
        B, S, H, Hkv, hd = 1, 64, 8, 3, 32         # 8 % 3 != 0
        q = jnp.zeros((B, S, H, hd))
        k = jnp.zeros((B, S, Hkv, hd))
        with pytest.raises(ValueError, match="H=8.*Hkv=3"):
            ops.flash_attention(q, k, k)

    @pytest.mark.parametrize("eps", [1e-3, 0.5])
    def test_rmsnorm_eps_threaded(self, eps):
        """ops.rmsnorm forwards a non-default eps to the kernel (the silent
        bug class this PR removes: kwargs accepted but dropped)."""
        x = jax.random.normal(jax.random.fold_in(KEY, 53), (4, 64))
        s = jax.random.normal(jax.random.fold_in(KEY, 54), (64,))
        o = ops.rmsnorm(x, s, eps=eps)
        tier = ops.TOLERANCE_TIERS["rmsnorm"]
        np.testing.assert_allclose(o, ref.rmsnorm_reference(x, s, eps=eps),
                                   **tier)
        # with a large eps the default-eps oracle must NOT match — proves the
        # value actually reached the kernel
        assert not np.allclose(o, ref.rmsnorm_reference(x, s), **tier)


class TestFusedAdam:
    @pytest.mark.parametrize("n", [128, 33, 4097])
    @pytest.mark.parametrize("step", [1, 7])
    def test_vs_hot_path_oracle(self, n, step):
        """fused_adam == optim.adam.adam_update_flat_np within its tier
        (n=33/4097 exercise the lane-padding path)."""
        from repro.optim.adam import AdamConfig, adam_update_flat_np
        acfg = AdamConfig()
        rng = np.random.default_rng(n * 10 + step)
        g = rng.standard_normal(n).astype(np.float32)
        st = {"master": rng.standard_normal(n).astype(np.float32),
              "mu": (rng.standard_normal(n) * 0.01).astype(np.float32),
              "nu": np.abs(rng.standard_normal(n) * 0.01).astype(np.float32)}
        m, mu, nu = ops.fused_adam(
            jnp.asarray(g), jnp.asarray(st["master"]), jnp.asarray(st["mu"]),
            jnp.asarray(st["nu"]), step=step, b1=acfg.b1, b2=acfg.b2,
            eps=acfg.eps, lr=acfg.lr, weight_decay=acfg.weight_decay)
        want = adam_update_flat_np(g, st, step, acfg)
        tier = ops.TOLERANCE_TIERS["fused_adam"]
        np.testing.assert_allclose(m, want["master"], **tier)
        np.testing.assert_allclose(mu, want["mu"], **tier)
        np.testing.assert_allclose(nu, want["nu"], **tier)

    def test_shape_mismatch_raises(self):
        z = jnp.zeros(8)
        with pytest.raises(ValueError, match="mismatched operand shapes"):
            ops.fused_adam(z, z, z, jnp.zeros(9), step=1)


class TestKernelCorpus:
    def test_all_cases_within_declared_tier(self):
        """The shared corpus (kernels/check.py) — same rows the
        KernelConsistencyChecker spot-checks and CI gates on."""
        from repro.kernels.check import check_kernels
        rows = check_kernels(seed=0)
        assert {r["kernel"] for r in rows} == set(ops.TOLERANCE_TIERS)
        bad = [r for r in rows if not r["within_tolerance"]]
        assert not bad, f"kernel cases outside declared tier: {bad}"
