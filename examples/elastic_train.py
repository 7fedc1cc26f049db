"""End-to-end elastic training driver.

Trains a configurable decoder-only model on the deterministic corpus for a
few hundred steps while a scripted fault schedule (fail-stop at 1/3 of the
run, fail-slow at 2/3) exercises the full ElasWave recovery path:
Agent detection -> ScheduleEngine multi-dim plan -> communicator edit ->
live remap -> layer migration -> dataflow/DVFS/RNG application.

    PYTHONPATH=src python examples/elastic_train.py \
        [--steps 200] [--dmodel 256] [--layers 8] [--report-every 10]

At the default size this is a ~10M-param model; --dmodel 896 --layers 12
gives ~100M (slow on CPU — sized down by default for the container).
"""
import argparse
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.cluster import VirtualCluster
from repro.models.config import ModelConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--dmodel", type=int, default=256)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=2048)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--report-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = ModelConfig(name="elastic-demo", family="dense",
                      num_layers=args.layers, d_model=args.dmodel,
                      num_heads=args.dmodel // 64 or 2,
                      num_kv_heads=max((args.dmodel // 64 or 2) // 2, 1),
                      d_ff=args.dmodel * 4, vocab_size=args.vocab,
                      dropout_rate=0.05, dtype="float32",
                      rope_theta=10000.0)
    print(f"model: {cfg.param_count() / 1e6:.1f}M params, "
          f"{args.steps} steps, global_batch={args.global_batch}")

    cl = VirtualCluster(cfg, dp=4, pp=2, global_batch=args.global_batch,
                        num_micro=2, seq_len=args.seq, seed=0)
    fail_stop_at = args.steps // 3
    fail_slow_at = 2 * args.steps // 3
    t0 = time.time()
    for step in range(args.steps):
        if step == fail_stop_at:
            print(f"-- step {step}: FAIL-STOP injected at rank (dp=2, stage=0)")
            cl.inject_fail_stop(2, 0)
            rec = cl.detect_and_recover()
            print(f"   recovered: MTTR={rec['total']:.3f}s "
                  f"(comm={rec['communicator']:.3f}s remap={rec['remap']:.4f}s "
                  f"migration={rec['migration']:.3f}s) rng_moves={rec['rng_moves']}")
        if step == fail_slow_at:
            print(f"-- step {step}: FAIL-SLOW injected (1.4x) at (dp=0, stage=1)")
            cl.inject_fail_slow(0, 1, 1.4)
            rec = cl.recover_fail_slow(0, 1, 1.4)
            print(f"   rebalanced: migration stall={rec['migration']:.3f}s")
        loss = cl.train_step()
        if step % args.report_every == 0 or step == args.steps - 1:
            dt = time.time() - t0
            print(f"step {step:4d}  loss={loss:.4f}  "
                  f"({dt / (step + 1) * 1e3:.0f} ms/step)")
    first, last = cl.losses[0], np.mean(cl.losses[-10:])
    print(f"\nloss {first:.4f} -> {last:.4f} "
          f"({'converging OK' if last < first else 'NOT converging'})")
    print(f"recoveries: {len(cl.recoveries)}; "
          f"final step time (simulated cluster): {cl.simulate_step_time():.3e}s")


if __name__ == "__main__":
    main()
