"""Host AdamW (``optim.adam.adam_update_flat_np``) over block sizes and
thread counts, the sweep that sets the module's ``BLOCK`` and ``THREADS``.

    PYTHONPATH=src python -m benchmarks.host_adam

Times one update of ``ELEMENTS`` float32 elements (half of the two-layer
mamba2-2.7b cut's 112,604,128 parameters, about one stage) for every block
size and thread count, with fresh outputs and in place, best of three, and
checks each against the single-block, single-thread update bit for bit.  Prints the host's cores
first, then one CSV row a setting (``benchmarks.common.emit``).  Uses no
accelerator: run it on the host whose AdamW is being tuned.
"""
from __future__ import annotations

import os
import platform
import time
from pathlib import Path

import numpy as np

from repro.optim import adam
from .common import emit

ELEMENTS = 56_302_064
BLOCKS = (1 << 16, 1 << 17, 1 << 18, 1 << 19, 1 << 20)


def host_cores() -> str:
    quota = Path("/sys/fs/cgroup/cpu.max")
    return (f"# {platform.processor() or platform.machine()}: "
            f"{len(os.sched_getaffinity(0))} cores in affinity, "
            f"{os.cpu_count()} online, cgroup cpu.max "
            f"{quota.read_text().strip() if quota.exists() else 'absent'}")


def configure(block: int, threads: int) -> None:
    adam.BLOCK, adam.THREADS, adam.THRESHOLD = block, threads, 0
    if adam._pool is not None:
        adam._pool.shutdown()
        adam._pool = None


def best_of(fn, reps: int = 3) -> float:
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def main(n: int = ELEMENTS) -> None:
    print(host_cores(), flush=True)
    rng = np.random.default_rng(0)
    g = rng.standard_normal(n, dtype=np.float32)
    state = {"master": rng.standard_normal(n, dtype=np.float32),
             "mu": rng.standard_normal(n, dtype=np.float32) * 0.01,
             "nu": np.abs(rng.standard_normal(n, dtype=np.float32)) * 0.01}
    cfg = adam.AdamConfig()
    configure(max(n, 1), 1)
    want = adam.adam_update_flat_np(g, state, 7, cfg)
    t = best_of(lambda: adam.adam_update_flat_np(g, state, 7, cfg))
    emit(f"host_adam[n={n},block=all,threads=1,out=fresh]", t * 1e6,
         f"ns_per_element={t * 1e9 / max(n, 1):.3f}")
    cores = len(os.sched_getaffinity(0))
    threads = sorted({k for k in (1, 2, 4, 6, 8, 12, 16, 24, cores)
                      if k <= cores})
    for block in BLOCKS:
        for k in threads:
            configure(block, k)
            for label in ("fresh", "in_place"):
                st = ({c: v.copy() for c, v in state.items()}
                      if label == "in_place" else state)
                out = st if label == "in_place" else None
                got = adam.adam_update_flat_np(g, st, 7, cfg, out=out)
                same = all(np.array_equal(got[c], want[c]) for c in want)
                t = best_of(lambda: adam.adam_update_flat_np(
                    g, st, 7, cfg, out=out))
                emit(f"host_adam[n={n},block={block},threads={k},"
                     f"out={label}]", t * 1e6,
                     f"ns_per_element={t * 1e9 / max(n, 1):.3f};"
                     f"bit_identical={same}")


if __name__ == "__main__":
    main()
