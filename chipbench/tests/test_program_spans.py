"""The reader of the program's spans: per-step sums, the share of the
chip's idle time under a leaf span by interval arithmetic, and no reading
where the trace holds no program span."""
import shutil
import types
from pathlib import Path

import pytest

from chipbench import harness as H
from chipbench import program_spans as P
from chipbench import trace as T

DATA = Path(__file__).parent / "data"
MS = 1_000_000
METRICS = ["step_grad_fetch_s", "step_host_optimizer_s", "step_snapshot_s",
           "step_transfer_bytes", "recovery_state_move_s"]


def _ctx(summary):
    return types.SimpleNamespace(trace={}, program_spans=summary)


def test_sums_per_window_step():
    spans = [
        ("repro.step", 0, 40 * MS, {"step": 7}),          # before the window
        ("repro.step.fetch", 10 * MS, 20 * MS, {"nbytes": 5}),
        ("repro.step", 50 * MS, 70 * MS, {"step": 8}),
        ("repro.step.fetch", 52 * MS, 54 * MS, {"nbytes": 100}),
        ("repro.step.adam", 55 * MS, 58 * MS, {"elements": 10, "stage": 0}),
        ("repro.step.adam", 58 * MS, 60 * MS, {"elements": 20, "stage": 1}),
        ("repro.step.writeback", 60 * MS, 61 * MS, {"nbytes": 40}),
        ("repro.step", 75 * MS, 95 * MS, {"step": 9}),
        ("repro.step.fetch", 76 * MS, 80 * MS, {"nbytes": 100}),
        ("repro.step.writeback", 80 * MS, 81 * MS, {"nbytes": 40}),
        ("repro.recover.remap", 71 * MS, 74 * MS, {"nbytes": 9}),
    ]
    s = P.summarize({}, spans, (45 * MS, 100 * MS))
    assert s["steps"] == 2
    assert s["idle"] is None                       # no device plane
    fetch = s["spans"]["repro.step.fetch"]
    assert fetch == {"seconds": pytest.approx(0.006), "calls": 2,
                     "nbytes": 200}
    # labels (the step, the stage) are not summed
    assert s["spans"]["repro.step.adam"] == {
        "seconds": pytest.approx(0.005), "calls": 2, "elements": 30}
    ctx = _ctx(s)
    assert P.per_step(ctx, "seconds", "step.fetch") == pytest.approx(0.003)
    assert P.per_step(ctx, "nbytes", "step.inputs", "step.fetch",
                      "step.writeback") == 140
    assert P.total(ctx, "seconds", "recover.verify", "recover.remap") \
        == pytest.approx(0.003)
    assert P.total(ctx, "seconds", "recover.migration") is None
    lines = P.report(s)
    fetch_line = next(l for l in lines if "repro.step.fetch:" in l)
    assert fetch_line.startswith("[spans] repro.step.fetch: 2 calls, "
                                 "seconds 0.006")
    assert fetch_line.endswith("nbytes 200 (100.0)")
    assert lines[-1] == "[spans] coverage: the trace holds no device plane"


def test_coverage_by_interval_arithmetic():
    """Chip 0 idle from 0-10, 30-60, 80-95 and 100-120 ms of the window;
    the leaf spans cover 5-10, 30-44 and 90-95 of it.  The step around them
    covers everything to 100 ms and is no leaf; after it the window holds
    no program work.  Labelled by its midpoint (45 ms), the 30-60 gap would
    count whole or not at all, not 14 ms."""
    device = {0: [(10 * MS, 30 * MS), (60 * MS, 80 * MS), (95 * MS, 100 * MS),
                  (120 * MS, 130 * MS)],
              1: [(0, 120 * MS)]}                   # another chip: not read
    spans = [("repro.step", 0, 100 * MS, {}),
             ("repro.step.device", 5 * MS, 44 * MS, {}),
             ("repro.step.fetch", 90 * MS, 97 * MS, {})]
    s = P.summarize(device, spans, (0, 125 * MS))
    covered = 0.005 + 0.014 + 0.005
    assert s["idle"]["steps"] == (pytest.approx(0.010 + 0.030 + 0.015),
                                  pytest.approx(covered))
    assert s["idle"]["window"] == (pytest.approx(0.055 + 0.020),
                                   pytest.approx(covered))
    assert [l[0] for l in P.leaves(spans)] == ["repro.step.device",
                                               "repro.step.fetch"]
    line = P.report(s)[-1]
    assert "in the window chip 0 idle 0.075" in line and "31.99999" in line
    assert "to the end of its last step chip 0 idle 0.055" in line
    assert "43.63636" in line


def test_intersection_of_interval_lists():
    xs = [(0, 10), (20, 30), (40, 50)]
    ys = [(5, 25), (28, 45)]
    assert P._intersect(xs, ys) == [(5, 10), (20, 25), (28, 30), (40, 45)]
    assert P._intersect(xs, []) == []


@pytest.fixture
def trace_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(P, "TRACE_DIR", tmp_path)
    return tmp_path


@pytest.mark.skipif(not (DATA / "v5e_small.xplane.pb").exists(),
                    reason="no recorded chip trace")
def test_no_program_span_reads_nothing(trace_dir, capsys):
    """The recorded chip trace has a window and device ops, and no program
    span (a program from before them): every reader returns None."""
    shutil.copy(DATA / "v5e_small.xplane.pb", trace_dir / "t.xplane.pb")
    bench = H.Bench.load()
    ctx = types.SimpleNamespace(trace={})
    assert [bench.reader(m).read(ctx) for m in METRICS] == [None] * 5
    assert "[spans] no program span" in capsys.readouterr().err
    # chip 0's idle time is what the trace reduction leaves of the window
    device, spans, window = P.read_trace(trace_dir / "t.xplane.pb")
    s = P.summarize(device, spans, window)
    r = T.reduce(trace_dir / "t.xplane.pb", chips=1)
    assert s["idle"]["window"] == (
        pytest.approx(r["window_s"] - r["busy_s"]), 0)
    assert s["idle"]["steps"] == s["idle"]["window"] and s["steps"] == 0


def test_untraced_run_reads_nothing(trace_dir):
    bench = H.Bench.load()
    ctx = types.SimpleNamespace(trace=None)
    assert [bench.reader(m).read(ctx) for m in METRICS] == [None] * 5


def test_reads_the_program_spans_of_a_trace(trace_dir, capsys):
    """A trace recorded here on the CPU: two steps inside the window, one
    before it, with the program's own ``span``."""
    import jax
    import jax.numpy as jnp
    from repro.spans import span
    x = jnp.ones(1000)

    def step(i):
        with span("step", step=i):
            with span("step.fetch", nbytes=1000):
                x.sum().block_until_ready()
            with span("step.writeback") as sp:
                sp.set_metadata(nbytes=24)

    jax.profiler.start_trace(str(trace_dir), profiler_options=T.profile_options())
    step(0)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        step(1)
        step(2)
    jax.profiler.stop_trace()
    bench = H.Bench.load()
    ctx = types.SimpleNamespace(trace={})
    assert bench.reader("step_transfer_bytes").read(ctx) == 1024
    assert bench.reader("step_grad_fetch_s").read(ctx) > 0
    assert bench.reader("step_snapshot_s").read(ctx) is None
    assert ctx.program_spans["steps"] == 2
    err = capsys.readouterr().err
    assert "[spans] 2 window steps" in err
    assert "[spans] repro.step.fetch: 2 calls" in err
