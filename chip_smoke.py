"""Bring-up check of the elastic trainer on a TPU.

    python chip_smoke.py             # one chip: kernels, then elastic training
    python chip_smoke.py --chips 4   # four chips: the sharded train step only

One process, no children (a chip belongs to one process).  The model is
CodeQwen1.5-7B at its published widths with two cuts, printed on an early
line: 2 layers (one per pipeline stage) and an eighth of the vocabulary (one
chip's share of an 8-way split; the VirtualCluster keeps ~36 B of fp32
optimizer state, snapshot and gradient per parameter on the host).  This is a
bring-up, not a benchmark cell.  Weights are random from ``--seed``; tokens
come from ``data/pipeline.py``.

Phases (one chip):

1. device check: platform, kind, count, jax version; no TPU -> exit 2, no
   result line;
2. kernels: ``kernels/check.py``'s corpus and each kernel at the training
   path's widths, compiled by Mosaic, against ``kernels/ref.py`` at full f32
   matmul precision, each error beside its ``TOLERANCE_TIERS`` entry;
3. elastic training with the kernels on: a fault-free ``VirtualCluster``
   takes 4 steps; a second, same seed, takes 1, loses worker (dp=1, stage=0)
   to a fail-stop, recovers through ``detect_and_recover`` and takes 3 more.
   Checks: finite losses, elastic == fault-free within ``SAME_MATH_RTOL``,
   step 0 == a float32 reference within ``BF16_RTOL``, and Mosaic kernels in
   the compiled step program.

With ``--chips 4`` only the sharded train step of ``launch/steps.py`` runs
(``compile_sharded``), on a (data=2, model=2) mesh, against the same step
compiled for device 0 alone.

The last line of a passing run is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import resource
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402

from repro import configs                                       # noqa: E402
from repro.compile_cache import enable_compile_cache            # noqa: E402
from repro.core.cluster import VirtualCluster                   # noqa: E402
from repro.data.pipeline import (GlobalBatchSampler,            # noqa: E402
                                 materialize_samples)
from repro.kernels.check import case_row, kernel_cases, width_cases  # noqa: E402
from repro.models import registry as R                          # noqa: E402
from repro.models.config import ATTN, ModelConfig               # noqa: E402

#: The cut (see the module docstring).
LAYERS = 2
VOCAB_SHARE = 8
SEQ = 2048
DP, PP, GLOBAL_BATCH, NUM_MICRO = 2, 2, 2, 1

#: Two runs of the same bf16 math in different programs (the elastic run's
#: post-recovery micro-batch of 2 against two of 1; a sharded step against an
#: unsharded one) differ only in the order of their sums: at most one bf16
#: rounding (unit roundoff 2^-8) of some elements, averaged over a step's
#: 4096 tokens.  One unit roundoff of the loss bounds that with room.
SAME_MATH_RTOL = 2.0 ** -8
#: The trainer rounds every activation and the logits to bf16 where the
#: float32 reference keeps 24 bits; that cannot move the loss by more than
#: one bf16 ulp of its size (eps 2^-7).
BF16_RTOL = 2.0 ** -7


def codeqwen_cut(layers: int = LAYERS, vocab_share: int = VOCAB_SHARE
                 ) -> ModelConfig:
    cfg = configs.get_config("codeqwen1p5_7b")
    return dataclasses.replace(cfg, num_layers=layers,
                               vocab_size=cfg.vocab_size // vocab_share)


def say(*parts) -> None:
    print(*parts, flush=True)


def device_info() -> Dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def device_bytes(device=None, key: str = "peak_bytes_in_use") -> Optional[int]:
    stats = (device or jax.devices()[0]).memory_stats()
    return None if stats is None else stats.get(key)


def host_memory() -> str:
    info = {}
    with open("/proc/meminfo") as f:
        for line in f:
            name, value = line.split(":", 1)
            info[name] = int(value.split()[0]) * 1024
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    return (f"host RAM total={info['MemTotal'] / 2**30:.1f} GiB "
            f"available={info['MemAvailable'] / 2**30:.1f} GiB "
            f"this process max RSS={rss / 2**30:.2f} GiB")


def close(a: float, b: float, rtol: float) -> bool:
    return abs(a - b) <= rtol * abs(b)


# ---------------------------------------------------------------------------
# phase 2: kernels
# ---------------------------------------------------------------------------
def kernels_phase(cases) -> bool:
    ok = True
    for case in cases:
        t0 = time.perf_counter()
        row = case_row(case)
        ok &= row["within_tolerance"]
        say(f"[kernel] {row['case']:42s} max_abs={row['max_abs_err']:.3e} "
            f"max_rel={row['max_rel_err']:.3e} tier rtol={row['rtol']:g} "
            f"atol={row['atol']:g} (uses {row['tier_use']:.3f} of it) "
            f"{'PASS' if row['within_tolerance'] else 'FAIL'} "
            f"({time.perf_counter() - t0:.1f} s incl. compile)")
    return ok


# ---------------------------------------------------------------------------
# phase 3: elastic training
# ---------------------------------------------------------------------------
def reference_loss(cl: VirtualCluster) -> float:
    """The next step's loss from a plain float32 forward of the cluster's
    parameters (``registry.make_train_loss``, no kernels, full precision)."""
    cfg = dataclasses.replace(cl.cfg, dtype="float32")
    assert cfg.block_pattern() == [((ATTN,), cfg.num_layers)], cfg.name
    stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *cl.layer_params)
    params = {"embed": cl.stem["embed"], "segments": [[stacked]],
              "final_norm": cl.head["final_norm"], "head": cl.head["head"]}
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    toks = jnp.asarray(materialize_samples(
        cl.sampler.sample_ids(cl.step_count), cl.seq, cfg.vocab_size))
    with jax.default_matmul_precision("highest"):
        return float(jax.jit(R.make_train_loss(cfg))(
            params, {"tokens": toks, "labels": toks}))


def custom_calls(programs) -> int:
    return sum(p.as_text().count("tpu_custom_call") for p in programs)


def train_run(cfg: ModelConfig, *, steps: int, seed: int, seq: int,
              fail_at: Optional[int] = None, reference: bool = False,
              label: str) -> Dict:
    """One VirtualCluster run with the kernels on; prints as it goes."""
    t0 = time.perf_counter()
    cl = VirtualCluster(cfg, dp=DP, pp=PP, global_batch=GLOBAL_BATCH,
                        num_micro=NUM_MICRO, seq_len=seq, seed=seed,
                        use_pallas=True)
    out: Dict = {"build_s": time.perf_counter() - t0}
    say(f"[{label}] built in {out['build_s']:.1f} s; {host_memory()}")
    if reference:
        t0 = time.perf_counter()
        out["ref_loss"] = reference_loss(cl)
        say(f"[{label}] float32 reference loss of step 0 = "
            f"{out['ref_loss']!r} ({time.perf_counter() - t0:.1f} s)")
    t0 = time.perf_counter()
    programs = cl.compile_step()
    out["compile_s"] = time.perf_counter() - t0
    out["custom_calls"] = custom_calls(programs)
    say(f"[{label}] step program compiled in {out['compile_s']:.1f} s; "
        f"tpu_custom_call in it: {out['custom_calls']}")
    out["losses"], out["step_s"] = [], []
    for step in range(steps):
        if step == fail_at:
            cl.inject_fail_stop(1, 0)
            t0 = time.perf_counter()
            rec = cl.detect_and_recover()
            out["recovery_s"] = time.perf_counter() - t0
            out["recovery_modeled_s"] = rec["total"]
            say(f"[{label}] fail-stop of worker (dp=1, stage=0) before step "
                f"{step}: recovery measured {out['recovery_s']:.2f} s wall "
                f"(host); modeled {rec['total']:.3f} s (core/cost_model.py "
                f"HardwareSpec, not a chip measurement); per-rank micro-batch "
                f"sizes now {cl.per_rank_mbs}")
            t0 = time.perf_counter()
            programs = cl.compile_step()
            out["recompile_s"] = time.perf_counter() - t0
            say(f"[{label}] recompile after recovery "
                f"{out['recompile_s']:.1f} s; tpu_custom_call in it: "
                f"{custom_calls(programs)}")
        t0 = time.perf_counter()
        out["losses"].append(float(cl.train_step()))
        out["step_s"].append(time.perf_counter() - t0)
        say(f"[{label}] step {step} loss={out['losses'][-1]!r} "
            f"wall={out['step_s'][-1]:.2f} s")
    out["peak_bytes"] = device_bytes()
    say(f"[{label}] device peak_bytes_in_use (since process start) = "
        f"{out['peak_bytes']}; {host_memory()}")
    return out


def elastic_phase(cfg: ModelConfig, *, seed: int, seq: int = SEQ,
                  steps: int = 4, fail_at: int = 1,
                  expect_mosaic: bool = True) -> bool:
    say("[elastic] step wall time: train_step ends in a device_get of the "
        "losses and gradients, so each step is synced; it includes the "
        "host-side Adam, the parameter upload and the ring snapshot, whose "
        "bf16 gradient round trip (fabric/snapshot.py) goes through the "
        "device")
    free = train_run(cfg, steps=steps, seed=seed, seq=seq, reference=True,
                     label="fault-free")
    gc.collect()        # the cluster's jit closures form a cycle
    elastic = train_run(cfg, steps=steps, seed=seed, seq=seq,
                        fail_at=fail_at, label="elastic")
    gc.collect()
    checks = {
        "losses finite": all(math.isfinite(x)
                             for x in free["losses"] + elastic["losses"]),
        f"elastic == fault-free (rtol {SAME_MATH_RTOL:.3g})": all(
            close(a, b, SAME_MATH_RTOL)
            for a, b in zip(elastic["losses"], free["losses"])),
        f"step 0 == float32 reference (rtol {BF16_RTOL:.3g})": close(
            free["losses"][0], free["ref_loss"], BF16_RTOL),
    }
    if expect_mosaic:
        checks["Mosaic kernels in the step program"] = (
            free["custom_calls"] > 0 and elastic["custom_calls"] > 0)
    diffs = [abs(a - b) / abs(b)
             for a, b in zip(elastic["losses"], free["losses"])]
    say(f"[elastic] relative loss differences elastic vs fault-free: {diffs}")
    say(f"[elastic] step 0 vs float32 reference: relative "
        f"{abs(free['losses'][0] - free['ref_loss']) / abs(free['ref_loss'])!r}")
    for name, passed in checks.items():
        say(f"[check] {name}: {'PASS' if passed else 'FAIL'}")
    return all(checks.values())


# ---------------------------------------------------------------------------
# --chips 4: the sharded train step
# ---------------------------------------------------------------------------
def _train_losses(cell, cfg: ModelConfig, *, steps: int, seed: int,
                  mesh) -> Dict:
    """``steps`` steps of the train cell compiled over ``mesh`` by
    ``launch/steps.py:compile_sharded``, its pspecs placing the state."""
    from repro.launch.steps import compile_sharded
    from repro.optim.adam import AdamConfig, init_opt_state
    batch, seq = cell.arg_shapes[2]["tokens"].shape
    step_fn = compile_sharded(cell, mesh)
    params_at, opt_at, batch_at = step_fn.in_shardings
    params = jax.jit(lambda k: R.init_model(k, cfg), out_shardings=params_at)(
        jax.random.key(seed))
    opt = jax.jit(lambda p: init_opt_state(p, AdamConfig()),
                  out_shardings=opt_at)(params)
    put = jax.jit(lambda b: b, out_shardings=batch_at)
    sampler = GlobalBatchSampler(batch, seed)
    losses, step_s = [], []
    for step in range(steps):
        toks = materialize_samples(sampler.sample_ids(step), seq,
                                   cfg.vocab_size)
        b = put({"tokens": toks, "labels": toks})
        t0 = time.perf_counter()
        params, opt, loss = step_fn(params, opt, b)
        losses.append(float(loss))
        step_s.append(time.perf_counter() - t0)
    devices = list(mesh.devices.flat)
    in_use = [device_bytes(d, "bytes_in_use") for d in devices]
    return {"losses": losses, "step_s": step_s, "bytes_in_use": in_use,
            "devices": [str(d) for d in devices]}


def sharded_phase(cfg: ModelConfig, *, seed: int, seq: int = SEQ,
                  batch: int = 2, steps: int = 3,
                  mesh_shape: Sequence[int] = (2, 2)) -> bool:
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import build_cell
    mesh = make_mesh(tuple(mesh_shape), ("data", "model"))
    cell = build_cell(cfg, "train", seq, batch, mesh)
    say(f"[sharded] train cell of launch/steps.py, parallel/sharding.py "
        f"pspecs, mesh {dict(mesh.shape)}, batch {batch} x seq {seq}, "
        f"remat on")
    one_chip = make_mesh((1, 1), ("data", "model"), jax.devices()[:1])
    runs = {}
    for name, m in (("sharded", mesh), ("unsharded", one_chip)):
        t0 = time.perf_counter()
        runs[name] = r = _train_losses(cell, cfg, steps=steps, seed=seed,
                                       mesh=m)
        gc.collect()
        say(f"[{name}] losses={r['losses']} step wall s={r['step_s']} "
            f"(compiled ahead; each ends in float(loss)); "
            f"{time.perf_counter() - t0:.1f} s in all")
        for d, nbytes in zip(r["devices"], r["bytes_in_use"]):
            say(f"[{name}] {d}: bytes_in_use={nbytes} (state live)")
    a, b = runs["sharded"]["losses"], runs["unsharded"]["losses"]
    checks = {
        "losses finite": all(math.isfinite(x) for x in a + b),
        f"sharded == unsharded (rtol {SAME_MATH_RTOL:.3g})": all(
            close(x, y, SAME_MATH_RTOL) for x, y in zip(a, b)),
    }
    say(f"[sharded] relative loss differences: "
        f"{[abs(x - y) / abs(y) for x, y in zip(a, b)]}")
    for name, passed in checks.items():
        say(f"[check] {name}: {'PASS' if passed else 'FAIL'}")
    return all(checks.values())


# ---------------------------------------------------------------------------
def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded train step on a 2x2 mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = device_info()
    say(f"[device] platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']} jax={jax.__version__}")
    if dev["platform"] != "tpu":
        say("[device] no TPU: this check runs only on the chip")
        return 2
    if dev["count"] < args.chips:
        say(f"[device] --chips {args.chips} needs {args.chips} devices")
        return 2
    say(f"[cache] persistent compilation cache: {enable_compile_cache()}")
    say(f"[host] {host_memory()}")
    cfg = codeqwen_cut()
    say(f"[model] {cfg.name} at published widths: d_model={cfg.d_model} "
        f"heads={cfg.num_heads}x{cfg.head_dim} kv_heads={cfg.num_kv_heads} "
        f"d_ff={cfg.d_ff} rope_theta={cfg.rope_theta:g} dtype={cfg.dtype}; "
        f"cut for a bring-up (not a benchmark cell): layers 32 -> "
        f"{cfg.num_layers} (one per stage), vocab {cfg.vocab_size * VOCAB_SHARE}"
        f" -> {cfg.vocab_size} (an eighth: host memory), seq {SEQ}, "
        f"dp={DP} pp={PP} global_batch={GLOBAL_BATCH} num_micro={NUM_MICRO}; "
        f"{cfg.param_count() / 1e9:.3f} B parameters, random from seed "
        f"{args.seed}")

    if args.chips == 4:
        ok = sharded_phase(cfg, seed=args.seed)
    else:
        ok = kernels_phase(kernel_cases(args.seed) + width_cases(args.seed))
        ok &= elastic_phase(cfg, seed=args.seed)
    if not ok:
        say("[result] a check failed")
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
