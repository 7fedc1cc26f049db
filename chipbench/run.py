"""One run of one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, no children.  In order: device check (a TPU with as many
chips as the cell asks for, or exit 2 with no result); the persistent
compilation cache; set-up (``drivers/<name>.py`` builds the cell from the seed,
compiles and warms every program the window runs, and takes the traffic's
first steps); the window (``--seconds`` of steps; a step under way at the
end finishes and counts; with ``--trace 1`` under the profiler); the peak
device memory; the program's state freed; the plain reference, which
decides ``correct``; the metrics.  The last line of standard output is the
result as one JSON object; the numbers compared, each beside its limit,
are the last lines of standard error and the last key of the result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                                  # noqa: E402
import gc                                                        # noqa: E402
import json                                                      # noqa: E402
import math                                                      # noqa: E402
import os                                                        # noqa: E402
import shutil                                                    # noqa: E402
import sys                                                       # noqa: E402
import types                                                     # noqa: E402
from pathlib import Path                                         # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import harness as H                               # noqa: E402

TRACE_DIR = ROOT / ".chipbench" / "trace"


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def device_info(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes(jax, n: int) -> int:
    """The allocator's peak of live buffers on the fullest chip."""
    peaks = []
    for d in jax.devices()[:n]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def temp_bytes(programs) -> int:
    """The largest scratch (``temp_size_in_bytes``) of the compiled
    programs the window ran.  On a TPU the allocator's statistics leave a
    program's scratch out, so the device's peak is the allocator's peak
    plus this."""
    sizes = [getattr(p.memory_analysis(), "temp_size_in_bytes", 0)
             for p in programs]
    return int(max(sizes, default=0))


def make_context(bench: H.Bench, workload: str, seed: int, spans,
                 before_last_setup_step=lambda: None):
    """What a driver is given.  ``before_last_setup_step`` is called before
    set-up's last step: a traced run starts the profiler there, so that the
    profiler's own start-up falls outside the window."""
    w = bench.workload(workload)
    spec = bench.config(w["config"])
    return types.SimpleNamespace(
        workload=w, spec=spec, cfg=H.model_config(spec),
        traffic=bench.traffic(w["traffic"]), seed=seed, spans=spans,
        reference=bench.reference(spec["reference"]), chips=w["chips"],
        before_last_setup_step=before_last_setup_step)


def reference_readings(ctx, n_steps: int, precision: str = "f32",
                       **kw) -> dict:
    """The plain reference following the program's first ``n_steps``
    (``kw`` as ``reference.common.train`` takes it)."""
    from chipbench.reference import common
    job, spec = ctx.traffic, ctx.spec
    gb = job.get("global_batch", job.get("batch"))
    vocab = ctx.cfg.vocab_size
    batches = [common.step_tokens(s, gb, job["seq"], vocab)
               for s in range(n_steps)]
    return common.train(ctx.reference.loss_fn(spec, precision),
                        lambda: ctx.reference.init_params(spec, ctx.seed),
                        batches, job["optimizer"], **kw)


def run(bench: H.Bench, workload: str, seed: int, seconds: float,
        trace: bool, check_device: bool = True) -> dict:
    import jax
    dev = device_info(jax)
    chips = bench.workload(workload)["chips"]
    if check_device and (dev["platform"] != "tpu" or dev["count"] < chips):
        say(f"[device] {dev}: this cell needs {chips} TPU chip(s)")
        raise SystemExit(2)
    from repro.compile_cache import enable_compile_cache
    say(f"[cache] {enable_compile_cache()}")

    from chipbench import trace as T

    def start_trace():
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR),
                                     profiler_options=T.profile_options())

    spans = H.Spans(annotate=trace)
    ctx = make_context(bench, workload, seed, spans, start_trace)
    driver = bench.driver(ctx.traffic["driver"]).Run(ctx)
    driver.setup()
    setup_s = time.perf_counter() - T_START
    say(f"[setup] {setup_s:.3f} s; set-up losses {driver.losses}")

    with spans.span("window"):
        win = driver.window(seconds)
    if trace:
        jax.profiler.stop_trace()
    live, temp = peak_bytes(jax, chips), temp_bytes(driver.programs)
    mem = live + temp
    n_win = len(win["steps"])
    say(f"[window] {n_win} steps in {win['t1'] - win['t0']:.3f} s")
    say(f"[memory] peak_bytes_in_use {live}; largest program scratch "
        f"{temp}; memory_peak_bytes {mem}")

    prog = driver.program_readings(n_win)
    driver.release()
    gc.collect()
    t_ref = time.perf_counter()
    with spans.span("reference"):
        ref = reference_readings(ctx, len(prog["losses"]),
                                 other_grads=prog.get("grads"))
    say(f"[reference] {len(prog['losses'])} steps in "
        f"{time.perf_counter() - t_ref:.1f} s; losses {ref['losses']}; "
        f"program {prog['losses']}")
    numbers = H.readings(prog, ref)
    correct, checks = H.judge(numbers, bench.limits(workload))

    rctx = types.SimpleNamespace(
        cfg=ctx.cfg, spec=ctx.spec, traffic=ctx.traffic, window=win,
        setup_s=setup_s, spans=spans, chips=chips, device=dev,
        trace=None, peaks=None)
    out_dev = dict(dev, count=chips, memory_peak_bytes=mem)
    breakdown = None
    if trace:
        peaks = H.load_json(bench.bench_dir / "peaks.json")
        if dev["kind"] not in peaks:
            raise KeyError(f"no peaks for device kind {dev['kind']!r}")
        rctx.peaks = peaks[dev["kind"]]
        rctx.trace = T.reduce(T.find_xspace(TRACE_DIR), chips=chips)
        out_dev.update(busy_s=rctx.trace["busy_s"],
                       window_s=rctx.trace["window_s"])
        breakdown = {"device_ops": rctx.trace["top_ops"][:10],
                     "idle_gaps": rctx.trace["idle_gaps"][:10]}
    metrics = {}
    for m in bench.metrics_for(workload, traced=trace):
        value = bench.reader(m["name"]).read(rctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    failed = sum(not math.isfinite(s["loss"]) for s in win["steps"])
    result = {"correct": correct, "attempted": n_win, "failed": failed,
              "metrics": metrics, "device": out_dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        extra = {k: v for k, v in numbers[name].items() if k != "value"}
        say(f"[check] {name} = {c['value']!r} limit {c['limit']!r} {extra}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    result = run(H.Bench.load(), args.workload, args.seed, args.seconds,
                 bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
