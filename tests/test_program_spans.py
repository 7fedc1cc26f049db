"""The program's spans (``repro/spans.py``) in a profiler trace on the CPU:
a tiny dp=2 pp=2 cluster compiles its step, trains one step, loses rank
(dp=1, stage 0) to a fail-stop, recovers through ``detect_and_recover`` and
trains one more step, under ``jax.profiler``."""
import glob

import jax
import pytest

from repro.core.cluster import VirtualCluster
from repro.models import registry as R

#: every span, with the span it must sit in (None: outermost)
PARENT = {
    "repro.compile.lower": None,
    "repro.compile.compile": None,
    "repro.step": None,
    "repro.step.inputs": "repro.step",
    "repro.step.device": "repro.step",
    "repro.step.fetch": "repro.step",
    "repro.step.accumulate": "repro.step",
    "repro.step.adam": "repro.step",
    "repro.step.writeback": "repro.step",
    "repro.step.snapshot": "repro.step",
    "repro.snapshot.adam": "repro.step.snapshot",
    "repro.snapshot.crc": "repro.step.snapshot",
    "repro.recover": None,
    "repro.recover.detect": "repro.recover",
    "repro.recover.plan": "repro.recover",
    "repro.recover.verify": "repro.recover",
    "repro.recover.communicator": "repro.recover",
    "repro.recover.remap": "repro.recover",
    "repro.recover.migration": "repro.recover",
    "repro.recover.dataflow": "repro.recover",
}


def _profile_options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(cluster, spans): each span a dict of its line, name, start, end
    (ns) and stats, in the order of their start."""
    cl = VirtualCluster(R.tiny_config("dense", num_layers=4), dp=2, pp=2,
                        global_batch=4, num_micro=1, seq_len=16, seed=0)
    d = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(d), profiler_options=_profile_options())
    try:
        cl.compile_step()
        cl.train_step()
        cl.inject_fail_stop(1, 0)
        assert cl.detect_and_recover() is not None
        cl.train_step()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(d / "**" / "*.xplane.pb"), recursive=True))[-1]
    spans = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            spans += [{"line": (plane.name, i), "name": e.name,
                       "start": e.start_ns, "end": e.end_ns,
                       "stats": dict(e.stats)}
                      for e in line.events if e.name.startswith("repro.")]
    return cl, sorted(spans, key=lambda s: (s["start"], -s["end"]))


def _inside(s, t):
    return (s is not t and s["line"] == t["line"]
            and t["start"] <= s["start"] and s["end"] <= t["end"])


def _enclosing(s, spans):
    """The innermost span around ``s``, or None."""
    around = [t for t in spans if _inside(s, t)]
    return min(around, key=lambda t: t["end"] - t["start"], default=None)


def test_every_span_appears_in_its_parent(traced):
    _, spans = traced
    assert {s["name"] for s in spans} == set(PARENT)
    for s in spans:
        parent = _enclosing(s, spans)
        assert (parent and parent["name"]) == PARENT[s["name"]], s["name"]


def test_the_steps_and_their_counts(traced):
    cl, spans = traced
    steps = [s for s in spans if s["name"] == "repro.step"]
    assert [s["stats"]["step"] for s in steps] == [0, 1]
    n_params = sum(st.total for st in cl.stages)
    for step, items in zip(steps, (2, 1)):     # one rank of stage 0 is gone
        kids = {}
        for s in spans:
            if _inside(s, step):
                kids.setdefault(s["name"], []).append(s["stats"])
        assert kids["repro.step.device"] == [{"items": items}]
        # per item, the flat gradient and the loss, in float32
        assert kids["repro.step.fetch"] == [
            {"nbytes": items * n_params * 4 + items * 4}]
        assert kids["repro.step.accumulate"] == [
            {"elements": items * n_params}]
        assert kids["repro.step.writeback"] == [{"nbytes": n_params * 4}]
        assert kids["repro.step.inputs"][0]["nbytes"] > 0
        adam = kids["repro.step.adam"]
        assert [a["stage"] for a in adam] == [0, 1]
        assert sum(a["elements"] for a in adam) == n_params
        # the ring snapshot ships every stage's gradient shards once
        assert sum(s["nbytes"] for s in kids["repro.step.snapshot"]) \
            == n_params * 4
        assert sum(s["elements"] for s in kids["repro.snapshot.adam"]) \
            == n_params
        # master, mu and nu hashed
        assert sum(s["nbytes"] for s in kids["repro.snapshot.crc"]) \
            == n_params * 12


def test_recovery_moves_state(traced):
    _, spans = traced
    by = {s["name"]: s["stats"] for s in spans
          if s["name"].startswith("repro.recover.")}
    for name in ("verify", "remap", "migration"):
        assert by[f"repro.recover.{name}"]["nbytes"] > 0, name


def _union(ivs):
    out = []
    for a, b in sorted(ivs):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def test_leaf_spans_cover_each_step(traced):
    """A leaf span has no program span inside it; the leaves account for
    the step's time."""
    _, spans = traced
    leaves = [s for s in spans
              if not any(_inside(t, s) for t in spans)]
    for step in (s for s in spans if s["name"] == "repro.step"):
        covered = sum(b - a for a, b in _union(
            [(t["start"], t["end"]) for t in leaves if _inside(t, step)]))
        assert covered >= 0.9 * (step["end"] - step["start"])
