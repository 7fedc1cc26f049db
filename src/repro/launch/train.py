"""Training launcher: real single-host training on a reduced config, or
--dryrun lowering of the full config on the production mesh.

    PYTHONPATH=src python -m repro.launch.train --arch nemotron_4_15b \
        --smoke --steps 20
    PYTHONPATH=src python -m repro.launch.train --arch llama3_405b --dryrun
"""
from __future__ import annotations

import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="train the reduced config on CPU")
    ap.add_argument("--dryrun", action="store_true",
                    help="lower+compile the full config on the 16x16 mesh")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    if args.dryrun:
        import os
        import subprocess
        import sys
        shape = "train_4k"
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", args.arch,
               "--shape", shape,
               "--mesh", "multi" if args.multi_pod else "single"]
        raise SystemExit(subprocess.call(cmd))

    import jax
    import numpy as np
    from repro import configs
    from repro.compile_cache import enable_compile_cache
    from repro.data.pipeline import GlobalBatchSampler, make_batch
    from repro.models import registry as R
    from repro.optim.adam import AdamConfig, adam_update, init_opt_state

    enable_compile_cache()
    cfg = configs.get_smoke_config(args.arch) if args.smoke else \
        configs.get_config(args.arch)
    print(f"training {cfg.name}: {cfg.param_count() / 1e6:.1f}M params")
    params = R.init_model(jax.random.key(0), cfg)
    adam = AdamConfig(lr=1e-3)
    opt = init_opt_state(params, adam)
    loss_fn = R.make_train_loss(cfg)

    @jax.jit
    def step_fn(params, opt, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        params, opt = adam_update(params, grads, opt, adam)
        return params, opt, loss

    ckpt = None
    if args.ckpt_dir:
        from repro.checkpoint import CheckpointManager
        ckpt = CheckpointManager(args.ckpt_dir)

    sampler = GlobalBatchSampler(args.batch)
    t0 = time.time()
    for step in range(args.steps):
        batch = make_batch(sampler.sample_ids(step), args.seq, cfg.vocab_size)
        if cfg.is_encdec:
            batch["frames"] = jax.random.normal(
                jax.random.fold_in(jax.random.key(9), step),
                (args.batch, args.seq, cfg.d_model))
        if cfg.frontend_embeds:
            batch["prefix_embeds"] = jax.random.normal(
                jax.random.fold_in(jax.random.key(9), step),
                (args.batch, cfg.frontend_embeds, cfg.d_model))
        params, opt, loss = step_fn(params, opt, batch)
        if step % 5 == 0 or step == args.steps - 1:
            print(f"step {step:4d} loss={float(loss):.4f} "
                  f"({(time.time() - t0) / (step + 1) * 1e3:.0f} ms/step)")
        if ckpt and step % 10 == 9:
            ckpt.save(step, params, opt, blocking=False)
    if ckpt:
        ckpt.wait()
        print(f"checkpoints: {sorted(p.name for p in ckpt.dir.glob('step_*'))}")


if __name__ == "__main__":
    main()
