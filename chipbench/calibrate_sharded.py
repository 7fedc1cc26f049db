"""Readings of the float8 control and the half-batch fault for a cell whose
reference keeps its state spread over its chips (not part of a benchmark
run).

    python3 chipbench/calibrate_sharded.py --workload <name> --seeds 1,2

``calibrate.py`` hands a variant's first gradient back to the reference
whole, on the default chip: at CodeQwen1.5-7B's widths and 8 layers that is
9.5 GB of float32 beside the reference's own state, more than a chip holds.
Here each first gradient is kept on the host and the norm of each leaf's
difference is taken there.  Per seed, with no program run, over the
traffic's set-up steps (the steps that ``correct`` compares), each variant's
numbers and whether the cell's limits call it ``correct`` (``H.judge``):

- ``control``: the reference computed with float8 matmuls, against the
  float32 reference;
- ``half_batch``: the reference trained on half of each batch (the mean
  taken over the rest), against it.

The program's own readings are ``run.py``'s ``[check]`` lines; an answer
altered by one bf16 unit reads the program's ``loss_gap`` moved by about
2^-7, and a state left unchanged reads 1 on ``change_gap``.  Each seed's
readings are one JSON line on standard output.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import harness as H                               # noqa: E402
from chipbench import run as R                                   # noqa: E402
from chipbench.reference.common import np_norm                   # noqa: E402

VARIANTS = {"control": {"precision": "fp8"},
            "half_batch": {"keep_batch": lambda b: b[:len(b) // 2]}}


def seed_readings(bench: H.Bench, workload: str, seed: int,
                  kinds=tuple(VARIANTS)) -> dict:
    ctx = R.make_context(bench, workload, seed, H.Spans())
    steps = ctx.traffic["setup_steps"]
    ref = R.reference_readings(ctx, steps, keep_grads=True)
    grads = ref.pop("grads")
    out = {"seed": seed, "steps": steps}
    for kind in kinds:
        other = R.reference_readings(ctx, steps, keep_grads=True,
                                     **VARIANTS[kind])
        theirs = other.pop("grads")
        diff = {k: np_norm(theirs[k] - grads[k]) for k in grads}
        got = H.readings(other, dict(ref, grad_diff_norms=diff))
        correct, _ = H.judge(got, bench.limits(workload))
        out[kind] = dict({k: v["value"] for k, v in got.items()},
                         correct=correct)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--kinds", default=",".join(VARIANTS))
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
        r = seed_readings(bench, args.workload, int(s),
                          tuple(args.kinds.split(",")))
        print(json.dumps(dict(r, workload=args.workload, device=dev)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
