"""Multi-pod dry-run driver — see ``_DOC`` below for the full usage text
(kept separate because the XLA device-count env var must be set before any
jax import, and the argparse help reuses it)."""
import os
os.environ["XLA_FLAGS"] = os.environ.get("DRYRUN_XLA_FLAGS",
                                         "--xla_force_host_platform_device_count=512")
# the 512 devices are virtual CPUs: pin the platform so neither this process
# nor the --all children it starts ever open an attached accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede any jax import: jax locks the device count on first init.

_DOC = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell:
  with mesh:
      lowered = jax.jit(step, in_shardings=..., out_shardings=...,
                        donate_argnums=...).lower(*input_specs)
      compiled = lowered.compile()
      memory_analysis / cost_analysis -> artifact JSON

Artifacts land in artifacts/dryrun/<arch>__<shape>__<mesh>.json and feed
benchmarks/roofline.py and EXPERIMENTS.md §Dry-run.

Usage:
  python -m repro.launch.dryrun --arch llama3_405b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--jobs 4]
"""

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

ART_DIR = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

MODEL_FLOPS_NOTE = ("MODEL_FLOPS = 6*N*D dense / 6*N_active*D MoE "
                    "(train); 2*N*D serving fwd")


def _layer_variants(cfg):
    """Two reduced-depth variants (L1, L2) whose cost difference isolates one
    repeat unit of the scanned segments — used to undo XLA's count-scan-body-
    once cost analysis by exact linear extrapolation to the full depth."""
    import dataclasses as _dc
    if cfg.is_encdec:
        c1 = _dc.replace(cfg, num_layers=1, encoder_layers=1, decoder_layers=1,
                         scan_layers=False)
        c2 = _dc.replace(cfg, num_layers=2, encoder_layers=2, decoder_layers=2,
                         scan_layers=False)
        return c1, c2, 1, 2, cfg.encoder_layers or cfg.num_layers
    period = 1
    if cfg.family == "hybrid":
        period = cfg.attn_period
    L1 = cfg.first_k_dense + period
    L2 = cfg.first_k_dense + 2 * period
    c1 = _dc.replace(cfg, num_layers=L1, scan_layers=False)
    c2 = _dc.replace(cfg, num_layers=L2, scan_layers=False)
    return c1, c2, L1, L2, cfg.num_layers


def _compile_cell(cfg, shape_name, seq, batch, mesh, remat=True):
    import jax
    from repro.launch.steps import build_cell
    from repro.parallel.sharding import to_shardings
    cell = build_cell(cfg, shape_name, seq, batch, mesh, remat=remat)
    in_sh = tuple(to_shardings(mesh, p) for p in cell.arg_pspecs)
    out_sh = to_shardings(mesh, cell.out_pspecs)
    with mesh:
        lowered = jax.jit(cell.fn, in_shardings=in_sh, out_shardings=out_sh,
                          donate_argnums=cell.donate).lower(*cell.arg_shapes)
        compiled = lowered.compile()
    return lowered, compiled


def _cell_costs(compiled):
    from repro.launch import hlo_analysis as H
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    coll = H.collective_bytes(compiled.as_text())
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": coll["total"],
            "coll_by_kind": coll}


def extrapolated_costs(cfg, shape_name, seq, batch, mesh, remat=True):
    """Per-device (flops, bytes, collective bytes) at FULL depth, by linear
    extrapolation over two reduced-depth compiles (scan bodies are counted
    once by XLA's cost analysis; depth enters linearly)."""
    c1, c2, L1, L2, Lf = _layer_variants(cfg)
    _, k1 = _compile_cell(c1, shape_name, seq, batch, mesh, remat=remat)
    _, k2 = _compile_cell(c2, shape_name, seq, batch, mesh, remat=remat)
    a, b = _cell_costs(k1), _cell_costs(k2)
    out = {}
    for key in ("flops", "bytes", "coll"):
        delta = (b[key] - a[key]) / (L2 - L1)
        out[key] = a[key] + delta * (Lf - L1)
    out["coll_by_kind"] = {
        k: a["coll_by_kind"][k] + (b["coll_by_kind"][k] - a["coll_by_kind"][k])
        / (L2 - L1) * (Lf - L1)
        for k in a["coll_by_kind"]}
    return out


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             overrides: dict | None = None) -> dict:
    import jax
    import numpy as np
    from repro import configs
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import build_cell, shape_kind
    from repro.launch import hlo_analysis as H
    from repro.parallel.sharding import to_shardings

    cfg = configs.get_config(arch)
    shapes = {n: (s, b) for n, s, b in cfg.shapes}
    skip = {n: why for n, why in cfg.skip_shapes}
    if shape_name in skip:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped", "reason": skip[shape_name]}
    seq, batch = shapes[shape_name]
    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    remat = True
    if overrides:
        import dataclasses as _dc
        overrides = dict(overrides)
        remat = overrides.pop("remat", True)
        if overrides:
            cfg = _dc.replace(cfg, **overrides)
    cell = build_cell(cfg, shape_name, seq, batch, mesh, remat=remat)
    in_sh = tuple(to_shardings(mesh, p) for p in cell.arg_pspecs)
    out_sh = to_shardings(mesh, cell.out_pspecs)
    with mesh:
        lowered = jax.jit(cell.fn, in_shardings=in_sh, out_shardings=out_sh,
                          donate_argnums=cell.donate).lower(*cell.arg_shapes)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    # ---- analyses ----
    try:
        mem = compiled.memory_analysis()
        mem_d = {k: int(getattr(mem, k)) for k in
                 ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes")
                 if hasattr(mem, k)}
    except Exception as e:   # pragma: no cover
        mem_d = {"error": str(e)}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        cost = {k: float(v) for k, v in ca.items()
                if k in ("flops", "bytes accessed", "transcendentals",
                         "optimal_seconds")}
    except Exception as e:   # pragma: no cover
        cost = {"error": str(e)}
    # NOTE: cost_analysis() and the compiled HLO are PER-DEVICE after SPMD
    # partitioning (verified empirically) — so the roofline denominators are
    # per-chip rates (chips=1); the formulas in the spec are equivalent with
    # HLO_FLOPs_global = per_device * chips.  XLA counts scan bodies ONCE, so
    # depth-dependent costs come from two-point extrapolation over reduced
    # depths (exact: depth enters linearly).
    ext = extrapolated_costs(cfg, shape_name, seq, batch, mesh, remat=remat)
    flops = ext["flops"]
    bytes_acc = ext["bytes"]
    coll = ext["coll_by_kind"]
    terms = H.roofline_terms(flops, bytes_acc, ext["coll"], chips=1)

    # model flops (useful-work denominator)
    kind = shape_kind(shape_name)
    n_active = cfg.active_param_count()
    tokens = batch * seq if kind != "decode" else batch
    model_flops = (6 if kind == "train" else 2) * n_active * tokens

    # analytic per-chip state footprint
    n_total = cfg.param_count()
    state_bytes = n_total * (2 + 12 if kind == "train" else 2)
    art = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "chips": chips,
        "status": "ok", "kind": kind,
        "seq": seq, "batch": batch,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory_analysis": mem_d,
        "cost_analysis": cost,
        "collective_bytes": coll,
        "roofline": terms,
        "model_flops": model_flops,
        "hlo_flops_per_device": flops,
        "hlo_flops_global": flops * chips,
        "useful_fraction": model_flops / (flops * chips) if flops else None,
        "params_total": n_total, "params_active": n_active,
        "state_bytes_per_chip": state_bytes / chips,
        "note": MODEL_FLOPS_NOTE,
    }
    return art


def cell_list(mesh_kinds):
    from repro import configs
    cells = []
    for arch in configs.ARCH_IDS:
        cfg = configs.get_config(arch)
        for (name, _, _) in cfg.shapes:
            for mk in mesh_kinds:
                cells.append((arch, name, mk))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=4)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg field override key=value (perf iterations); "
                         "also accepts remat=false")
    ap.add_argument("--tag", default=None,
                    help="artifact tag: writes to artifacts/perf/ instead")
    args = ap.parse_args()
    ART_DIR.mkdir(parents=True, exist_ok=True)

    mesh_kinds = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if not args.all:
        assert args.arch and args.shape
        overrides = {}
        for ov in args.override:
            k, v = ov.split("=", 1)
            if v.lower() in ("true", "false"):
                v = v.lower() == "true"
            else:
                try:
                    v = int(v)
                except ValueError:
                    try:
                        v = float(v)
                    except ValueError:
                        pass
            overrides[k] = v
        art = run_cell(args.arch, args.shape, mesh_kinds[0],
                       overrides=overrides or None)
        if args.tag:
            art["tag"] = args.tag
            art["overrides"] = {k: str(v) for k, v in overrides.items()}
            pdir = ART_DIR.parent / "perf"
            pdir.mkdir(parents=True, exist_ok=True)
            out = pdir / (f"{args.arch}__{args.shape}__{mesh_kinds[0]}"
                          f"__{args.tag}.json")
        else:
            out = ART_DIR / f"{args.arch}__{args.shape}__{mesh_kinds[0]}.json"
        out.write_text(json.dumps(art, indent=2))
        print(json.dumps(art, indent=2))
        if art["status"] == "ok":
            print(f"OK {args.arch} {args.shape} {mesh_kinds[0]} "
                  f"bottleneck={art['roofline']['bottleneck']}")
        return

    # orchestrate subprocesses (each needs its own 512-device jax runtime)
    cells = cell_list(mesh_kinds)
    pending = []
    for (arch, shape, mk) in cells:
        out = ART_DIR / f"{arch}__{shape}__{mk}.json"
        if out.exists() and not args.force:
            continue
        pending.append((arch, shape, mk, out))
    print(f"{len(pending)} cells to run ({len(cells) - len(pending)} cached)")
    procs = []

    def launch(arch, shape, mk, out):
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", arch, "--shape", shape, "--mesh", mk]
        log = out.with_suffix(".log").open("w")
        return subprocess.Popen(cmd, stdout=log, stderr=subprocess.STDOUT), \
            (arch, shape, mk, out)

    i = 0
    while i < len(pending) or procs:
        while i < len(pending) and len(procs) < args.jobs:
            procs.append(launch(*pending[i])); i += 1
        done = [p for p in procs if p[0].poll() is not None]
        for p, meta in done:
            procs.remove((p, meta))
            status = "OK" if meta[3].exists() else f"FAIL(rc={p.returncode})"
            print(f"[{status}] {meta[0]} {meta[1]} {meta[2]}", flush=True)
        time.sleep(1.0)


if __name__ == "__main__":
    main()
