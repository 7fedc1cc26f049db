"""Kernel-vs-ref gate: timing + tolerance-tier conformance per Pallas kernel.

Runs the shared comparison corpus (``repro.kernels.check``) in interpret mode
on CPU, times both sides best-of-reps, and **exits nonzero** if any case
exceeds its declared tier in ``repro.kernels.ops.TOLERANCE_TIERS`` — this is
the CI gate for the kernel layer.

Interpret-mode timings measure the Pallas *interpreter* on CPU, not TPU
kernel performance; they are trajectory data (is interpret overhead stable
across commits?), never a speedup claim.  The ``within_tolerance`` column is
the load-bearing one.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

from repro.kernels.check import case_row, kernel_cases
from .common import emit

REPS = 3


def bench_kernels(seed: int = 0, reps: int = REPS) -> list:
    """One row per corpus case: the ``check.case_row`` comparison fields plus
    ``kernel_ms`` / ``ref_ms`` best-of-reps wall clock."""
    rows = []
    for case in kernel_cases(seed):
        row = case_row(case)        # also warms both sides
        best_k = best_r = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            case.run_kernel()
            best_k = min(best_k, time.perf_counter() - t0)
            t0 = time.perf_counter()
            case.run_ref()
            best_r = min(best_r, time.perf_counter() - t0)
        row["kernel_ms"] = best_k * 1e3
        row["ref_ms"] = best_r * 1e3
        rows.append(row)
    return rows


def run(verbose: bool = True, seed: int = 0) -> list:
    rows = bench_kernels(seed)
    if verbose:
        print(f"  {'case':34s} {'kernel_ms':>10s} {'ref_ms':>8s} "
              f"{'max_abs_err':>12s} {'tier':>16s} {'ok':>3s}")
        for r in rows:
            tier = f"{r['rtol']:g}/{r['atol']:g}"
            print(f"  {r['case']:34s} {r['kernel_ms']:10.2f} "
                  f"{r['ref_ms']:8.2f} {r['max_abs_err']:12.3e} "
                  f"{tier:>16s} {'ok' if r['within_tolerance'] else 'FAIL':>3s}")
    return rows


def main(out_path: str = "BENCH_kernel_ref.json") -> int:
    t0 = time.perf_counter()
    rows = run()
    us = (time.perf_counter() - t0) * 1e6
    Path(out_path).write_text(json.dumps({"rows": rows}, indent=2) + "\n")
    failures = [r["case"] for r in rows if not r["within_tolerance"]]
    emit("kernel_ref", us,
         f"cases={len(rows)};tier_failures={len(failures)}")
    if failures:
        print(f"FAIL: kernel(s) outside declared tolerance tier: {failures}")
    return len(failures)


if __name__ == "__main__":
    raise SystemExit(main())
