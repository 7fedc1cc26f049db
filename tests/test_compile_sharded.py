"""``launch/steps.py:compile_sharded`` on four virtual CPU devices (fixed at
jax start-up, hence the subprocess): a train cell with q/k/v bias compiled
over a (data=2, model=2) mesh places its state by the cell's pspecs, and
under a profiler trace its compile and each step are spans carrying the
compiled program's collective bytes a step; over one device there are
none.  ``hlo_analysis.executed_collective_bytes`` counts a layer loop's
collectives once a trip, on module texts written as the CPU's and the
TPU's compilers print them."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

SCRIPT = r'''
import glob, json, sys, tempfile
sys.path.insert(0, {src!r})
import jax, numpy as np
from jax.profiler import ProfileData
from repro.launch.hlo_analysis import executed_collective_bytes
from repro.launch.mesh import make_mesh
from repro.launch.steps import build_cell, compile_sharded
from repro.models import registry as R
from repro.optim.adam import AdamConfig, init_opt_state

cfg = R.tiny_config("dense", qkv_bias=True)
mesh = make_mesh((2, 2), ("data", "model"))
cell = build_cell(cfg, "train", 16, 4, mesh)
d = tempfile.mkdtemp()
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
opts.host_tracer_level = 2
jax.profiler.start_trace(d, profiler_options=opts)
step = compile_sharded(cell, mesh)
at_p, at_o, at_b = step.in_shardings
params = jax.jit(lambda k: R.init_model(k, cfg), out_shardings=at_p)(
    jax.random.key(0))
opt = jax.jit(lambda p: init_opt_state(p, AdamConfig()),
              out_shardings=at_o)(params)
toks = np.arange(64, dtype=np.int32).reshape(4, 16) % cfg.vocab_size
losses = []
for _ in range(2):
    params, opt, loss = step(params, opt, jax.device_put(
        {{"tokens": toks, "labels": toks}}, at_b))
    losses.append(float(loss))
jax.profiler.stop_trace()
spans = []
for plane in ProfileData.from_file(glob.glob(d + "/**/*.xplane.pb",
                                             recursive=True)[0]).planes:
    for line in plane.lines:
        for e in line.events:
            if e.name.startswith("repro."):
                spans.append([e.name, dict(e.stats)])
one = compile_sharded(build_cell(cfg, "train", 16, 4, mesh),
                      make_mesh((1, 1), ("data", "model"), jax.devices()[:1]))
bq = params["segments"][0][0]["attn"]["bq"]
print("RESULT " + json.dumps({{
    "spans": spans, "losses": losses, "counter": step.collective_bytes,
    "text": executed_collective_bytes(step.compiled.as_text())["total"],
    "one_chip": one.collective_bytes, "calls": step.calls,
    "bq_devices": len(bq.sharding.device_set),
    "bq_shard": list(bq.addressable_shards[0].data.shape)}}))
'''


def test_compile_sharded_spans_carry_collective_bytes():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(src=str(ROOT / "src"))],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    r = json.loads([l for l in proc.stdout.splitlines()
                    if l.startswith("RESULT ")][-1][len("RESULT "):])
    assert r["counter"] == r["text"] > 0
    assert r["one_chip"] == 0
    assert r["calls"] == 2 and all(x == x for x in r["losses"])
    # the bias [L, H*hd] sharded over `model`: half of H*hd a chip
    assert r["bq_devices"] == 4 and r["bq_shard"] == [4, 32]
    compiles = [s for n, s in r["spans"] if n == "repro.compile.sharded"]
    steps = [s for n, s in r["spans"] if n == "repro.sharded.step"]
    assert [s["collective_bytes"] for s in compiles] == [r["counter"]]
    assert [s["step"] for s in steps] == [0, 1]
    assert all(s["collective_bytes"] == r["counter"] for s in steps)


MODULE = """HloModule step

%add (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %s = f32[] add(%x, %y)
}

%cond (p: (s32[], f32[8])) -> pred[] {
  %p = (s32[]{:T(128)}, f32[8]{0:T(256)}) parameter(0)
  %i = s32[]{:T(128)} get-tuple-element(%p), index=0
  %n = s32[]{:T(128)} constant(5)
  ROOT %lt = pred[]{:T(512)} compare(%i, %n), direction=LT
}

%body (q: (s32[], f32[8])) -> (s32[], f32[8]) {
  %q = (s32[]{:T(128)}, f32[8]{0:T(256)}) parameter(0)
  %j = s32[]{:T(128)} get-tuple-element(%q), index=0
  %one = s32[]{:T(128)} constant(1)
  %next = s32[]{:T(128)} add(%j, %one)
  %v = f32[8]{0:T(256)} get-tuple-element(%q), index=1
  %all-reduce.1 = f32[8]{0:T(256)} all-reduce(%v), replica_groups={{0,1}}, to_apply=%add
  ROOT %t = (s32[]{:T(128)}, /*index=1*/f32[8]{0:T(256)}) tuple(%next, %all-reduce.1)
}

%all-reduce-scatter (input: bf16[4,6]) -> bf16[2,6] {
  %input = bf16[4,6]{1,0:T(8,128)(2,1)} parameter(0)
  %all-reduce.2 = bf16[4,6]{1,0:T(8,128)(2,1)} all-reduce(%input), replica_groups={{0,1}}, to_apply=%add
  ROOT %slice = bf16[2,6]{1,0} slice(%all-reduce.2), slice={[0:2], [0:6]}
}

ENTRY %main (a: f32[8], g: bf16[4,6]) -> (f32[8], bf16[2,6], bf16[8,6]) {
  %a = f32[8]{0:T(256)} parameter(0)
  %g = bf16[4,6]{1,0:T(8,128)(2,1)} parameter(1)
  %zero = s32[]{:T(128)} constant(0)
  %zero.copy = s32[]{:T(128)} copy(%zero)
  %init = (s32[]{:T(128)}, f32[8]{0:T(256)}) tuple(%zero.copy, %a)
  %while.1 = (s32[]{:T(128)}, f32[8]{0:T(256)}) while(%init), condition=%cond, body=%body
  %fusion.1 = bf16[2,6]{1,0} fusion(%g), kind=kCustom, calls=%all-reduce-scatter
  %all-gather-start.1 = (bf16[4,6]{1,0}, bf16[8,6]{1,0}) all-gather-start(%g), replica_groups={{0,1}}, dimensions={0}
  %all-gather-done.1 = bf16[8,6]{1,0} all-gather-done(%all-gather-start.1)
  %r = f32[8]{0:T(256)} get-tuple-element(%while.1), index=1
  ROOT %out = (f32[8]{0}, bf16[2,6]{1,0}, bf16[8,6]{1,0}) tuple(%r, %fusion.1, %all-gather-done.1)
}
"""


def test_executed_collective_bytes_counts_a_loop_once_a_trip():
    """The TPU's form: no ``known_trip_count``, so the trips are read from
    ``i < 5`` over an induction variable that starts at a copy of 0 and
    steps by 1.  The loop's all-reduce (f32[8], 32 bytes) runs 5 times; the
    fusion that calls ``%all-reduce-scatter`` starts its operand (bf16[4,6],
    48 bytes) once and the all-reduce inside it is not counted again; the
    asynchronous all-gather counts at its start (its operand, 48 bytes)."""
    from repro.launch.hlo_analysis import executed_collective_bytes as count
    assert count(MODULE) == {"all-reduce": 5 * 32 + 48, "all-gather": 48,
                             "total": 5 * 32 + 48 + 48}
    # the CPU's form: the compiler's own trip count wins
    cpu = MODULE.replace(
        "condition=%cond, body=%body",
        'condition=%cond, body=%body, backend_config={"known_trip_count"'
        ':{"n":"3"},"known_init_step":{"init":"0","step":"1"}}')
    assert count(cpu)["all-reduce"] == 3 * 32 + 48


def test_executed_collective_bytes_refuses_a_loop_it_cannot_count():
    from repro.launch.hlo_analysis import executed_collective_bytes as count
    for old, new in (("direction=LT", "direction=GT"),     # counts down
                     ("add(%j, %one)", "multiply(%j, %one)"),
                     ("constant(1)", "constant(2)")):       # steps by 2
        with pytest.raises(ValueError, match="trips of while.1"):
            count(MODULE.replace(old, new))
    with pytest.raises(ValueError, match="branches"):
        count(MODULE.replace(
            "%r = f32[8]{0:T(256)} get-tuple-element(%while.1), index=1",
            "%r = f32[8]{0:T(256)} conditional(%zero, %a), "
            "branch_computations={%body}"))
