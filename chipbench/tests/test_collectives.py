"""The layer ``collectives`` of the four-chip cell: the readers of
``step_collective_bytes`` (the operand bytes of the collectives on the
chips' op lines) and ``step_exposed_collective_s`` (the time the op lines
spend in them), and the model operations of ``codeqwen1p5_7b_8l``."""
import re
import shutil
import types
from pathlib import Path

import pytest

from chipbench import collectives as C
from chipbench import flops
from chipbench import harness as H
from chipbench import trace as T

METRICS = H.BENCH_DIR / "metrics"
DATA = Path(__file__).parent / "data"
MS = 1_000_000
RECORDED_STEP_BYTES = 45_230_576


def reader(name):
    return H.load_module(METRICS / f"{name}.py")


def test_exposed_collectives_union_per_chip_averaged_over_chips():
    exposed = reader("step_exposed_collective_s").exposed
    ops = {
        0: [("%all-gather-start.1 = (bf16[2]{0}, bf16[4]{0}) "
             "all-gather-start(bf16[2]{0} %p), replica_groups={{0,1}}",
             0, 2 * MS),
            ("%all-gather-done.1 = bf16[4]{0} all-gather-done(%ags.1)",
             1 * MS, 5 * MS),                       # overlaps the start
            ("%fusion.3 = bf16[4]{0} fusion(%all-reduce.2), kind=kLoop",
             5 * MS, 9 * MS),                       # consumes one: compute
            ("%all-reduce.2 = f32[8]{0} all-reduce(f32[8]{0} %x), "
             "to_apply=%add", 10 * MS, 13 * MS),
            ("%all-reduce.9 = f32[8]{0} all-reduce(f32[8]{0} %x)",
             40 * MS, 60 * MS)],                    # past the window's end
        1: [("%reduce-scatter-fusion.1 = bf16[2]{0} fusion(bf16[4]{0} %g), "
             "kind=kOutput", 0, 4 * MS),            # a fusion named for one
            ("%copy.1 = bf16[2]{0} copy(%a)", 4 * MS, 8 * MS),
            ("%fusion.12 = bf16[8]{0} fusion(%fusion.217), kind=kCustom, "
             "calls=%all-reduce-scatter", 10 * MS, 12 * MS),   # a collective
            ("%fusion.40 = (bf16[4]{0}, f32[8]{0}) fusion(%p, %q), "
             "kind=kCustom, calls=%async_collective_fusion.541",
             20 * MS, 30 * MS),                     # compute beside one
            ("%async-collective-done = f32[8]{0} fusion(%g), kind=kCustom, "
             "calls=%fused_computation.446", 30 * MS, 32 * MS)],
        2: [("%all-to-all.1 = bf16[2]{0} all-to-all(%a)", 0, 9 * MS)],
    }
    secs, names, seen = exposed(ops, (0, 50 * MS), chips=2)
    # chip 0: [0, 5], [10, 13] and [40, 50]; chip 1: [0, 4], [10, 12] and
    # [30, 32]; chip 2 unused
    assert secs == pytest.approx((18 + 8) / 2 * 1e-3)
    assert dict(names) == {"all-gather-start.1": 1, "all-gather-done.1": 1,
                           "all-reduce.2": 1, "all-reduce.9": 1,
                           "reduce-scatter-fusion.1": 1, "fusion.12": 1,
                           "async-collective-done": 1}
    assert seen == 10


def test_exposed_collectives_reader_needs_a_trace_and_steps():
    read = reader("step_exposed_collective_s").read
    assert read(types.SimpleNamespace(trace=None, window={"steps": [{}]})) \
        is None
    assert read(types.SimpleNamespace(trace={}, window={"steps": []})) \
        is None


def test_recorded_four_chip_trace(tmp_path, monkeypatch):
    """A trace recorded on a 2x2 TPU v5e host (``record_collectives.py``):
    three steps of the program's sharded train step at small widths.  The
    chip names its collectives as the reader matches them: plain
    all-reduces and all-gathers, a collective-permute's start and done, a
    fusion that calls an all-reduce-scatter, and the start and done of an
    asynchronous collective; the fusion that overlaps one with compute
    (``calls=%async_collective_fusion``) is not counted."""
    mod = reader("step_exposed_collective_s")
    path = DATA / "v5e_2x2_sharded.xplane.pb"
    device, host = T.read_xspace(path)
    assert sorted(device) == [0, 1, 2, 3]
    window = [(a, b) for n, a, b in host if n == "chipbench.window"][-1]
    secs, names, seen = mod.exposed(device, window, chips=4)
    kinds = {re.sub(r"\.\d+$", "", n) for n in names}
    assert kinds == {"all-reduce", "all-gather", "collective-permute-start",
                     "collective-permute-done", "fusion",
                     "async-collective-start", "async-collective-done"}
    overlapped = [h for ops in device.values() for h, _, _ in ops
                  if "calls=%async_collective_fusion" in h]
    assert overlapped and not any(C.is_collective(h) for h in overlapped)
    busy = T.reduce(path, chips=4)["busy_s"]
    assert secs == pytest.approx(0.00391439225, rel=1e-9)
    assert 0 < secs < busy
    assert seen == 10476
    # through ``read``: the window's seconds over its three steps
    shutil.copy(path, tmp_path / "run.xplane.pb")
    monkeypatch.setattr(C, "TRACE_DIR", tmp_path)
    ctx = types.SimpleNamespace(trace={}, window={"steps": [{}] * 3},
                                chips=4)
    assert mod.read(ctx) == pytest.approx(secs / 3, rel=1e-12)


def test_collective_bytes_per_sharded_step():
    """Operand bytes, from the types the events print, of what each op
    starts: a collective op or its start (a tuple operand summed, TPU
    layouts and all), a fusion that is one; never a ``-done``, a fusion
    that carries one beside compute, or an op that only reads a result."""
    started = reader("step_collective_bytes").started
    ops = {
        0: [("%all-gather-start.1 = (bf16[2]{0}, bf16[4]{0}) "
             "all-gather-start(bf16[2]{0} %p), replica_groups={{0,1}}",
             0, 2 * MS),                                        # 4 bytes
            ("%all-gather-done.1 = bf16[4]{0} all-gather-done((bf16[2]{0}, "
             "bf16[4]{0}) %all-gather-start.1)", 1 * MS, 5 * MS),
            ("%fusion.3 = bf16[4]{0} fusion(f32[8]{0} %all-reduce.2), "
             "kind=kLoop", 5 * MS, 9 * MS),
            ("%all-reduce.2 = (bf16[4,8]{1,0:T(8,128)(2,1)S(1)}, /*index=1*/"
             "f32[8]{0:T(256)}) all-reduce(bf16[4,8]{1,0:T(8,128)(2,1)S(1)} "
             "%x, f32[8]{0:T(256)} %y), replica_groups=[2,2]<=[4], "
             "to_apply=%add", 10 * MS, 13 * MS),                # 64 + 32
            ("%all-reduce.9 = f32[8]{0} all-reduce(f32[8]{0} %x)",
             60 * MS, 70 * MS)],                    # past the window's end
        1: [("%fusion.12 = bf16[8]{0} fusion(bf16[16]{0} %fusion.217), "
             "kind=kCustom, calls=%all-reduce-scatter", 0, 2 * MS),    # 32
            ("%async-collective-start = (f32[4]{0}, f32[8]{0}, u32[]{:S(2)}) "
             "fusion(f32[4]{0} %g), kind=kCustom, calls=%fused_computation.9",
             2 * MS, 3 * MS),                                   # 16
            ("%async-collective-done = f32[8]{0} fusion(f32[4]{0} %a, "
             "f32[8]{0} %b), kind=kCustom, calls=%fused_computation.10",
             3 * MS, 4 * MS),
            ("%fusion.40 = (bf16[4]{0}, f32[8]{0}) fusion(bf16[4]{0} %p, "
             "f32[8]{0} %q), kind=kCustom, calls=%async_collective_fusion.5",
             4 * MS, 6 * MS),
            ("%collective-permute-start = (s32[2]{0}, s32[2]{0}, u32[], u32[]) "
             "collective-permute-start(s32[2]{0} %f), "
             "source_target_pairs={{0,1},{1,0}}", 6 * MS, 7 * MS)],   # 8
        2: [("%all-to-all.1 = bf16[2]{0} all-to-all(bf16[2]{0} %a)",
             0, 9 * MS)],
    }
    nbytes, kinds = started(ops, (0, 50 * MS), chips=2)
    assert nbytes == (4 + 96 + 32 + 16 + 8) / 2
    assert dict(kinds) == {"all-gather-start": 2, "all-reduce": 48,
                           "fusion": 16, "async-collective-start": 8,
                           "collective-permute-start": 4}
    read = reader("step_collective_bytes").read
    assert read(types.SimpleNamespace(trace=None, window={"steps": [{}]})) \
        is None
    assert read(types.SimpleNamespace(trace={}, window={"steps": []})) \
        is None


def test_collective_bytes_of_the_recorded_trace(tmp_path, monkeypatch):
    """The recording's three steps: every chip starts the same bytes a step,
    45,230,576 (the compiled step's own count, ``tests/test_tpu_compile.py``,
    reads the same), and ``read`` gives them a window step."""
    path = DATA / "v5e_2x2_sharded.xplane.pb"
    shutil.copy(path, tmp_path / "run.xplane.pb")
    monkeypatch.setattr(C, "TRACE_DIR", tmp_path)
    mod = reader("step_collective_bytes")
    device, window = C.window_ops(types.SimpleNamespace(trace={}))
    per_chip = [mod.started(device, window, chips=4)[0]] + [
        mod.started({c: device[c]}, window, chips=1)[0] for c in range(4)]
    assert per_chip == [3 * RECORDED_STEP_BYTES] * 5
    ctx = types.SimpleNamespace(trace={}, window={"steps": [{}] * 3},
                                chips=4)
    assert mod.read(ctx) == RECORDED_STEP_BYTES


def test_codeqwen1p5_7b_8l_operations():
    # per layer and token, forward: q, o 2*4096*4096 each, k, v 2*4096*512
    # each (4 kv heads of 128), attention 2 * 2 * 1024 * 4096 (causal half
    # of seq 2048), SwiGLU 3 * 2*4096*13440; head 2*4096*92416; the bias
    # adds are elementwise and not counted; training = 3x forward
    spec = H.load_json(H.BENCH_DIR / "configs" / "codeqwen1p5_7b_8l.json")
    layer = (2 * 2 * 4096 * 4096 + 2 * 2 * 4096 * 512
             + 2 * 2 * 1024 * 4096 + 3 * 2 * 4096 * 13440)
    hand = 3 * (8 * layer + 2 * 4096 * 92416)
    got = flops.model_flops_per_token(spec, 2048)
    assert got == pytest.approx(hand, rel=1e-12)
    assert got == pytest.approx(12.4e9, rel=0.01)


def test_the_cell_is_the_configuration_as_published():
    """8 of the 32 layers at published widths, 4 kv heads and q/k/v bias
    run where the registry has 32 and none."""
    spec = H.load_json(H.BENCH_DIR / "configs" / "codeqwen1p5_7b_8l.json")
    cfg = H.model_config(spec)
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.d_ff, cfg.vocab_size, cfg.qkv_bias) == \
        (8, 4096, 32, 4, 128, 13440, 92416, True)
    assert cfg.param_count() + 4096 == 2_380_378_112    # + the final norm
    del spec["registry_departs"]["qkv_bias"]
    with pytest.raises(ValueError, match="qkv_bias"):
        H.model_config(spec)
