"""BENCH_train_step — old-vs-new step and recovery wall clock.

The repo's first perf-trajectory artifact: times ``VirtualCluster.train_step``
and the recovery executor on the reduced workload used by
``benchmarks/snapshot_overhead.py`` (dp=2, pp=2, 4-layer tiny config), old
(seed, ``fast_path=False``) vs new (flat-state fast path), and emits
``BENCH_train_step.json``:

.. code-block:: json

    {
      "workload": {"dp": 2, "pp": 2, "num_layers": 4, "global_batch": 8,
                   "num_micro": 2, "seq_len": 16},
      "step":     {"ref_ms": ..., "fast_ms": ..., "speedup": ...},
      "recovery": {"fail_stop":         {"ref_ms": ..., "fast_ms": ..., "speedup": ...},
                   "scale_out":         {"ref_ms": ..., "fast_ms": ..., "speedup": ...},
                   "fail_slow_migrate": {"ref_ms": ..., "fast_ms": ..., "speedup": ...}},
      "pallas_step": {"jnp_ms": ..., "pallas_ms": ..., "interpret": true,
                      "loss_abs_diff": ...},
      "kernels": [{"kernel": ..., "case": ..., "kernel_ms": ..., "ref_ms": ...,
                   "max_abs_err": ..., "rtol": ..., "atol": ...,
                   "within_tolerance": true}, ...],
      "reps": 5, "steps_per_rep": 3
    }

Timings are best-of-reps (resists scheduler noise on shared machines); the
two paths are bit-identical in numerics (tests/test_fast_path_numerics.py),
so this measures pure implementation overhead.  Informational: consumers
should track the trajectory of ``speedup`` across commits, not gate on
absolute numbers — EXCEPT ``kernels[*].within_tolerance``, which is the
kernel-vs-ref numerics gate (``main`` exits nonzero on a violation, and CI
fails the build).  ``pallas_step`` runs the same workload with
``use_pallas=True``: on this CPU container the kernels execute under the
Pallas interpreter, so ``pallas_ms`` measures interpreter overhead, not TPU
speedup; ``loss_abs_diff`` is the observed pallas-vs-jnp divergence after
one step.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

from repro.core.cluster import VirtualCluster
from repro.models import registry as R
from .common import emit

WORKLOAD = dict(dp=2, pp=2, global_batch=8, num_micro=2, seq_len=16, seed=0)
NUM_LAYERS = 4
REPS = 5
STEPS_PER_REP = 3


def _mk(fast: bool, use_pallas: bool = False) -> VirtualCluster:
    cfg = R.tiny_config("dense", num_layers=NUM_LAYERS)
    return VirtualCluster(cfg, fast_path=fast, use_pallas=use_pallas,
                          **WORKLOAD)


def bench_step() -> dict:
    """Best-of-reps per-step wall time, interleaved so both paths see the
    same machine conditions."""
    cls = {fast: _mk(fast) for fast in (False, True)}
    for cl in cls.values():
        cl.run(1)       # compile / warm caches
    best = {False: float("inf"), True: float("inf")}
    for _ in range(REPS):
        for fast in (False, True):
            t0 = time.perf_counter()
            cls[fast].run(STEPS_PER_REP)
            best[fast] = min(best[fast],
                             (time.perf_counter() - t0) / STEPS_PER_REP)
    return {"ref_ms": best[False] * 1e3, "fast_ms": best[True] * 1e3,
            "speedup": best[False] / best[True]}


def bench_recovery() -> dict:
    """Wall clock of the recovery executor itself (plan + communicator edit
    + live remap + migration + dataflow): fail-stop, rejoin, and a
    migration-heavy fail-slow (layer rebalance — where the fast path's
    zero-rebuild of untouched stages pays), old vs new.  Fresh clusters per
    rep: recovery mutates group membership."""
    best = {k: {False: float("inf"), True: float("inf")}
            for k in ("fail_stop", "scale_out", "fail_slow_migrate")}
    for _ in range(REPS):
        for fast in (False, True):
            cl = _mk(fast)
            cl.run(1)
            t0 = time.perf_counter()
            cl.recover_fail_stop(1, 1)
            best["fail_stop"][fast] = min(best["fail_stop"][fast],
                                          time.perf_counter() - t0)
            t0 = time.perf_counter()
            cl.recover_scale_out(1, 1)
            best["scale_out"][fast] = min(best["scale_out"][fast],
                                          time.perf_counter() - t0)
            cl.inject_fail_slow(0, 0, 1.6)
            t0 = time.perf_counter()
            cl.recover_fail_slow(0, 0, 1.6)
            best["fail_slow_migrate"][fast] = min(
                best["fail_slow_migrate"][fast], time.perf_counter() - t0)
    return {k: {"ref_ms": v[False] * 1e3, "fast_ms": v[True] * 1e3,
                "speedup": v[False] / v[True]}
            for k, v in best.items()}


def bench_pallas_step(reps: int = 2, steps: int = 2) -> dict:
    """Per-step wall clock with the Pallas kernels in the hot path vs plain
    jnp, plus the observed loss divergence after the first step.  Fewer reps
    than the fast/legacy comparison: interpret-mode kernels are slow and this
    row is trajectory data, not a speedup claim."""
    from repro.kernels.ops import interpret_mode
    cls = {up: _mk(True, use_pallas=up) for up in (False, True)}
    loss = {up: float(cl.train_step()) for up, cl in cls.items()}  # + compile
    best = {False: float("inf"), True: float("inf")}
    for _ in range(reps):
        for up in (False, True):
            t0 = time.perf_counter()
            cls[up].run(steps)
            best[up] = min(best[up], (time.perf_counter() - t0) / steps)
    return {"jnp_ms": best[False] * 1e3, "pallas_ms": best[True] * 1e3,
            "interpret": interpret_mode(),
            "loss_abs_diff": abs(loss[True] - loss[False])}


def run(verbose: bool = True) -> dict:
    from .kernel_ref import bench_kernels
    step = bench_step()
    recovery = bench_recovery()
    pallas_step = bench_pallas_step()
    kernels = bench_kernels()
    result = {
        "workload": {**{k: v for k, v in WORKLOAD.items() if k != "seed"},
                     "num_layers": NUM_LAYERS},
        "step": step,
        "recovery": recovery,
        "pallas_step": pallas_step,
        "kernels": kernels,
        "reps": REPS,
        "steps_per_rep": STEPS_PER_REP,
    }
    if verbose:
        print(f"  step: ref={step['ref_ms']:.1f}ms fast={step['fast_ms']:.1f}ms "
              f"speedup={step['speedup']:.2f}x")
        for k, v in recovery.items():
            print(f"  {k}: ref={v['ref_ms']:.2f}ms fast={v['fast_ms']:.2f}ms "
                  f"speedup={v['speedup']:.2f}x")
        print(f"  pallas_step: jnp={pallas_step['jnp_ms']:.1f}ms "
              f"pallas={pallas_step['pallas_ms']:.1f}ms "
              f"(interpret={pallas_step['interpret']}) "
              f"loss_abs_diff={pallas_step['loss_abs_diff']:.3e}")
        for r in kernels:
            print(f"  kernel {r['case']:34s} err={r['max_abs_err']:.3e} "
                  f"{'ok' if r['within_tolerance'] else 'FAIL'}")
    return result


def main(out_path: str = "BENCH_train_step.json") -> int:
    t0 = time.perf_counter()
    result = run()
    us = (time.perf_counter() - t0) * 1e6
    Path(out_path).write_text(json.dumps(result, indent=2) + "\n")
    failures = [r["case"] for r in result["kernels"]
                if not r["within_tolerance"]]
    emit("bench_train_step", us,
         f"step_speedup={result['step']['speedup']:.2f}x;"
         f"failstop_speedup={result['recovery']['fail_stop']['speedup']:.2f}x;"
         f"kernel_tier_failures={len(failures)}")
    if failures:
        print(f"FAIL: kernel(s) outside declared tolerance tier: {failures}")
    return len(failures)


if __name__ == "__main__":
    raise SystemExit(main())
