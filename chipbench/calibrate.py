"""Readings from which a cell's limits are set (not part of a benchmark run).

    python3 chipbench/calibrate.py --workload <name> --seeds 1,2,3 --seconds 15

For each seed, in one process: the cell's set-up and a short window, then
the numbers that ``correct`` compares, read for

- ``program``: the program against the plain reference (the lower reading);
- ``control``: the reference computed with float8 matmuls put in the
  program's place (the nearest precision below the configuration's bf16);
- ``half_batch``: the reference trained on half of each batch, the mean
  taken over the rest, put in the program's place;
- ``answer_altered``: the program's losses, each altered by one bf16 unit
  (x (1 + 2^-7)) where the step returns it;
- ``state_unchanged``: a step that returns its state unchanged reads 1 on
  ``change_gap`` by construction (the program's change is zero), and is
  not run.

Each seed's readings are one JSON line on standard output.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import harness as H                               # noqa: E402
from chipbench import run as R                                   # noqa: E402

BF16_UNIT = 2.0 ** -7


def numbers(prog, ref) -> dict:
    return {k: v["value"] for k, v in H.readings(prog, ref).items()}


def seed_readings(bench: H.Bench, workload: str, seed: int, seconds: float,
                  kinds=("control", "half_batch")) -> dict:
    spans = H.Spans()
    ctx = R.make_context(bench, workload, seed, spans)
    driver = bench.driver(ctx.traffic["driver"]).Run(ctx)
    t0 = time.perf_counter()
    driver.setup()
    win = driver.window(seconds)
    prog = driver.program_readings(len(win["steps"]))
    driver.release()
    gc.collect()
    t1 = time.perf_counter()
    n = len(prog["losses"])
    ref = R.reference_readings(ctx, n, other_grads=prog.get("grads"))
    t2 = time.perf_counter()
    out = {"seed": seed, "steps": n, "program_s": t1 - t0,
           "reference_s": t2 - t1, "program": numbers(prog, ref)}
    altered = dict(prog, losses=[x * (1 + BF16_UNIT) for x in prog["losses"]])
    out["answer_altered"] = numbers(altered, ref)

    def in_programs_place(**kw):
        """A reference variant put in the program's place, read against
        the reference (which then also takes its first gradient)."""
        other = R.reference_readings(ctx, n, keep_grads=True, **kw)
        return numbers(other, R.reference_readings(
            ctx, n, other_grads=other.pop("grads")))

    if "control" in kinds:
        out["control"] = in_programs_place(precision="fp8")
    if "half_batch" in kinds:
        out["half_batch"] = in_programs_place(
            keep_batch=lambda b: b[:len(b) // 2])
    out["state_unchanged"] = {"change_gap": 1.0}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--kinds", default="control,half_batch")
    args = ap.parse_args(argv)
    import jax
    from repro.compile_cache import enable_compile_cache
    dev = R.device_info(jax)
    if dev["platform"] != "tpu":
        print(f"[device] {dev}: readings are taken on the chip",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    bench = H.Bench.load()
    for s in args.seeds.split(","):
        r = seed_readings(bench, args.workload, int(s), args.seconds,
                          tuple(args.kinds.split(",")))
        print(json.dumps(dict(r, workload=args.workload, device=dev)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
