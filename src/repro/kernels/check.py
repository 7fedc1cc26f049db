"""Kernel-vs-ref comparison cases — one corpus, three consumers.

Each :class:`KernelCase` pairs a Pallas kernel invocation with its
``kernels/ref.py`` (or numpy Adam) oracle on seeded inputs shaped like the
training hot path (GQA head ratio, SSD group broadcast, non-default eps).
``ops.TOLERANCE_TIERS`` declares the acceptance bound per kernel.

Consumers:
* ``core.invariants.KernelConsistencyChecker`` — spot-checks every kernel at
  cluster start before locksteping the pallas/jnp cluster twins;
* ``tests/test_kernels.py`` — tier conformance as a unit test;
* ``benchmarks/kernel_ref.py`` — times both sides and gates CI on the tiers.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from . import ops, ref


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """One comparison: ``run_kernel()`` / ``run_ref()`` -> list of f32 arrays
    (same order), judged under ``ops.TOLERANCE_TIERS[name]``."""
    name: str               # TOLERANCE_TIERS key
    label: str              # unique case id (a kernel can have many cases)
    run_kernel: Callable[[], List[np.ndarray]]
    run_ref: Callable[[], List[np.ndarray]]

    @property
    def tier(self) -> Dict[str, float]:
        return ops.TOLERANCE_TIERS[self.name]


def _np(outs) -> List[np.ndarray]:
    return [np.asarray(o, dtype=np.float32) for o in outs]


def kernel_cases(seed: int = 0) -> List[KernelCase]:
    k = jax.random.key(seed)
    ks = jax.random.split(k, 12)
    cases: List[KernelCase] = []

    # -- flash attention, GQA head ratio, causal + non-causal ---------------
    B, S, H, Hkv, hd = 2, 64, 8, 2, 32
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    kk = jax.random.normal(ks[1], (B, S, Hkv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd), jnp.float32)

    def flash_ref(causal):
        rep = H // Hkv
        kf = jnp.repeat(kk, rep, axis=2).transpose(0, 2, 1, 3).reshape(B * H, S, hd)
        vf = jnp.repeat(v, rep, axis=2).transpose(0, 2, 1, 3).reshape(B * H, S, hd)
        qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
        o = ref.mha_reference(qf, kf, vf, causal=causal)
        return _np([o.reshape(B, H, S, hd).transpose(0, 2, 1, 3)])

    for causal in (True, False):
        cases.append(KernelCase(
            "flash_attention",
            f"flash_attention[gqa,{'causal' if causal else 'bidir'}]",
            run_kernel=(lambda c=causal: _np(
                [ops.flash_attention(q, kk, v, causal=c)])),
            run_ref=(lambda c=causal: flash_ref(c))))

    # -- rmsnorm, non-default eps -------------------------------------------
    x = jax.random.normal(ks[3], (4, 16, 64), jnp.float32)
    scale = 1.0 + 0.1 * jax.random.normal(ks[4], (64,), jnp.float32)
    eps = 1e-3
    cases.append(KernelCase(
        "rmsnorm", "rmsnorm[eps=1e-3]",
        run_kernel=lambda: _np([ops.rmsnorm(x, scale, eps=eps)]),
        run_ref=lambda: _np([ref.rmsnorm_reference(x, scale, eps=eps)])))

    # -- ssd scan, group broadcast ------------------------------------------
    b, s, h, p, g, n = 2, 32, 4, 16, 2, 16
    sx = jax.random.normal(ks[5], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[6], (b, s, h), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[7], (h,), jnp.float32))
    Bm = jax.random.normal(ks[8], (b, s, g, n), jnp.float32)
    Cm = jax.random.normal(ks[9], (b, s, g, n), jnp.float32)

    def ssd_ref():
        rep = h // g
        Bh = jnp.repeat(Bm, rep, axis=2)
        Ch = jnp.repeat(Cm, rep, axis=2)
        y, _ = ref.ssd_reference(sx, dt, A, Bh, Ch)
        return _np([y])

    cases.append(KernelCase(
        "ssd_scan", "ssd_scan[groups]",
        run_kernel=lambda: _np(
            [ops.ssd_scan(sx, dt, A, Bm, Cm, chunk=8)[0]]),
        run_ref=ssd_ref))

    return cases + _adam_cases(seed, 4097)   # 4097: not a lane multiple


def _adam_cases(seed: int, nvec: int) -> List[KernelCase]:
    """Fused AdamW vs the host-numpy hot-path oracle over ``nvec`` elements."""
    from repro.optim.adam import AdamConfig, adam_update_flat_np
    acfg = AdamConfig()
    rng = np.random.default_rng(seed)
    gvec = rng.standard_normal(nvec).astype(np.float32)
    st = {"master": rng.standard_normal(nvec).astype(np.float32),
          "mu": (rng.standard_normal(nvec) * 0.01).astype(np.float32),
          "nu": np.abs(rng.standard_normal(nvec) * 0.01).astype(np.float32)}
    step = 7

    def adam_kernel():
        m, mu, nu = ops.fused_adam(
            jnp.asarray(gvec), jnp.asarray(st["master"]),
            jnp.asarray(st["mu"]), jnp.asarray(st["nu"]), step=step,
            b1=acfg.b1, b2=acfg.b2, eps=acfg.eps, lr=acfg.lr,
            weight_decay=acfg.weight_decay)
        return _np([m, mu, nu])

    def adam_ref():
        out = adam_update_flat_np(gvec, st, step, acfg)
        return _np([out["master"], out["mu"], out["nu"]])

    return [KernelCase("fused_adam", f"fused_adam[n={nvec}]",
                       run_kernel=adam_kernel, run_ref=adam_ref)]


def width_cases(seed: int = 0, *, flash=(1, 2048, 32, 128),
                rmsnorm=(2048, 4096), ssd=(1, 2048, 80, 64, 128, 256),
                adam_n: int = 4 * 1024 * 1024) -> List[KernelCase]:
    """The kernels at the training path's widths, in f32 (the tiers are f32
    bounds): flash attention ``(B, S, H, hd)`` (CodeQwen1.5-7B's heads),
    rmsnorm ``(rows, d)``, the SSD scan ``(b, s, h, p, n, chunk)``
    (mamba2-2.7b's) and fused AdamW over ``adam_n`` elements."""
    ks = jax.random.split(jax.random.key(seed), 8)
    B, S, H, hd = flash
    q, kk, v = (jax.random.normal(ks[i], (B, S, H, hd), jnp.float32)
                for i in range(3))

    def flash_ref():
        fold = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
        o = ref.mha_reference(fold(q), fold(kk), fold(v), causal=True)
        return _np([o.reshape(B, H, S, hd).transpose(0, 2, 1, 3)])

    rows, d = rmsnorm
    x = jax.random.normal(ks[3], (rows, d), jnp.float32)
    scale = 1.0 + 0.1 * jax.random.normal(ks[4], (d,), jnp.float32)

    b, s, h, p, n, chunk = ssd
    sx = jax.random.normal(ks[5], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[6], (b, s, h), jnp.float32)) * 0.1
    A = -jnp.exp(jnp.linspace(-1.0, 1.0, h, dtype=jnp.float32))
    Bm, Cm = (jax.random.normal(k_, (b, s, 1, n), jnp.float32) * n ** -0.5
              for k_ in jax.random.split(ks[7]))

    def ssd_ref():
        y, _ = ref.ssd_reference(sx, dt, A, jnp.repeat(Bm, h, axis=2),
                                 jnp.repeat(Cm, h, axis=2))
        return _np([y])

    return [
        KernelCase("flash_attention", f"flash_attention[{B}x{S}x{H}x{hd}]",
                   run_kernel=lambda: _np([ops.flash_attention(q, kk, v)]),
                   run_ref=flash_ref),
        KernelCase("rmsnorm", f"rmsnorm[{rows}x{d}]",
                   run_kernel=lambda: _np([ops.rmsnorm(x, scale)]),
                   run_ref=lambda: _np([ref.rmsnorm_reference(x, scale)])),
        KernelCase("ssd_scan", f"ssd_scan[h={h},p={p},n={n},chunk={chunk}]",
                   run_kernel=lambda: _np(
                       [ops.ssd_scan(sx, dt, A, Bm, Cm, chunk=chunk)[0]]),
                   run_ref=ssd_ref),
    ] + _adam_cases(seed, adam_n)


def case_row(case: KernelCase) -> Dict:
    """Run one case; returns the comparison row (no timing).  The kernel runs
    as the training path runs it; the oracle runs on the host's CPU backend
    at full f32 matmul precision.  On a TPU the oracle's own f32 arithmetic
    is too coarse to judge by: on a v5e the SSD oracle's recurrence was 2.6
    tiers from a float64 one where the kernel was 0.76."""
    got = case.run_kernel()
    with jax.default_device(jax.devices("cpu")[0]), \
            jax.default_matmul_precision("highest"):
        want = case.run_ref()
    tier = case.tier
    pairs = [(g, w) for g, w in zip(got, want) if g.size]
    max_err = max((float(np.max(np.abs(g - w))) for g, w in pairs),
                  default=0.0)
    # normwise relative error, and the worst element's share of its bound
    # (``allclose`` passes iff tier_use <= 1)
    max_rel = max((float(np.max(np.abs(g - w)) / max(np.max(np.abs(w)),
                                                     tier["atol"]))
                   for g, w in pairs), default=0.0)
    tier_use = max((float(np.max(np.abs(g - w)
                                 / (tier["atol"] + tier["rtol"] * np.abs(w))))
                    for g, w in pairs), default=0.0)
    within = all(np.allclose(g, w, rtol=tier["rtol"], atol=tier["atol"])
                 for g, w in zip(got, want))
    return {"kernel": case.name, "case": case.label,
            "max_abs_err": max_err, "max_rel_err": max_rel,
            "tier_use": tier_use, "rtol": tier["rtol"], "atol": tier["atol"],
            "within_tolerance": bool(within)}


def check_kernels(seed: int = 0) -> List[Dict]:
    """All comparison rows for one seed (raise-free; callers gate)."""
    return [case_row(c) for c in kernel_cases(seed)]
