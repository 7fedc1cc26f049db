"""Benchmark aggregator — one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows (plus per-benchmark detail)."""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import (analytic_scale, communicator_mttr,
                   convergence_consistency, failslow, kernel_ref,
                   lse_breakdown, migration_mttr, moe_case, proactive_mttr,
                   roofline, scenarios_suite, serve_bench, snapshot_overhead,
                   spot_trace, throughput_failstop, train_step_perf)
    print("name,us_per_call,derived")
    mods = [
        ("fig11", throughput_failstop),
        ("fig12a", lse_breakdown),
        ("fig12b", communicator_mttr),
        ("fig13", migration_mttr),
        ("table3", snapshot_overhead),
        ("sec7.5", convergence_consistency),
        ("fig14", spot_trace),
        ("fig15a", failslow),
        ("sec7.7", moe_case),
        ("roofline", roofline),
        ("kernel_ref", kernel_ref),
        ("scenarios", scenarios_suite),
        ("bench_step", train_step_perf),
        ("bench_serve", serve_bench),
        ("analytic_scale", analytic_scale),
        ("proactive", proactive_mttr),
    ]
    failed = []
    for name, mod in mods:
        try:
            rc = mod.main()
            # gate-style benchmarks (kernel_ref, train_step_perf) return a
            # nonzero violation count instead of raising
            if isinstance(rc, int) and rc:
                failed.append(name)
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
