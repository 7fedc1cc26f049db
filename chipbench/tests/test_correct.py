"""``correct`` at a size the CPU holds: the unbroken program passes; the
program with a fault planted under its timed path, and the float8 control
in its place, fail the cell's limits.

A step that returns its state unchanged, half of the batch left out (the
mean taken over the rest) and an answer altered where it is produced are
the faults a one-chip training cell can have; none of these cells has an
exchange between chips to leave out.
"""
import pytest

from chipbench import harness as H
from chipbench import run as R
from chipbench.tests import tiny

SEED = 2 ** 31 + 77
UNIT = 2.0 ** -7
ELASTIC = ["mamba2_elastic_steady", "mamba2_elastic_failstop"]
PLAIN = ["codeqwen7b_plain_step"]


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny.make(tmp_path_factory.mktemp("bench"))


def seconds(workload):
    # long enough on the CPU for the fail-stop event and a step after it
    return 4.0 if workload.endswith("failstop") else 0.5


def run(bench, workload):
    return R.run(bench, workload, SEED, seconds(workload), trace=False,
                 check_device=False)


@pytest.mark.parametrize("workload", ELASTIC + PLAIN)
def test_sound_program_is_correct(bench, workload):
    r = run(bench, workload)
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"


# ---- faults planted in the elastic trainer --------------------------------
def _elastic_fault(monkeypatch, fault):
    from repro.core import cluster as C
    if fault == "state_unchanged":
        monkeypatch.setattr(C, "adam_update_flat_np",
                            lambda g, st, step, cfg: st)
        return
    orig = C.VirtualCluster.train_step

    def step(self):
        if fault == "half_batch":
            n = len(self.grad_weights)
            self.grad_weights = [1.0] + [0.0] * (n - 1)
        loss = orig(self)
        return loss * (1 + UNIT) if fault == "answer_altered" else loss
    monkeypatch.setattr(C.VirtualCluster, "train_step", step)


def _plain_fault(monkeypatch, fault):
    from repro.launch import steps as S
    if fault == "state_unchanged":
        monkeypatch.setattr(S, "adam_update",
                            lambda params, grads, state, cfg: (params, state))
        return
    if fault == "half_batch":
        orig = S.R.make_train_loss

        def half(cfg, **kw):
            f = orig(cfg, **kw)
            return lambda p, b, rng_ctx=None: f(
                p, {k: v[:v.shape[0] // 2] for k, v in b.items()}, rng_ctx)
        monkeypatch.setattr(S.R, "make_train_loss", half)
        return
    orig_cell = S.build_cell

    def cell(*a, **kw):
        c = orig_cell(*a, **kw)
        fn = c.fn

        def altered(params, opt, batch):
            p, o, loss = fn(params, opt, batch)
            return p, o, loss * (1 + UNIT)
        c.fn = altered
        return c
    monkeypatch.setattr(S, "build_cell", cell)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
@pytest.mark.parametrize("workload", ELASTIC + PLAIN)
def test_planted_fault_is_not_correct(bench, workload, fault, monkeypatch):
    plant = _plain_fault if workload in PLAIN else _elastic_fault
    plant(monkeypatch, fault)
    r = run(bench, workload)
    assert not r["correct"], (fault, r["checks"])


@pytest.mark.parametrize("workload", ELASTIC + PLAIN)
def test_float8_control_is_not_correct(bench, workload):
    """The plain reference computed with float8 matmuls, in the program's
    place, against the float32 reference, judged by the cell's limits."""
    ctx = R.make_context(bench, workload, SEED, H.Spans())
    control = R.reference_readings(ctx, 3, "fp8", keep_grads=True)
    ref = R.reference_readings(ctx, 3, other_grads=control.pop("grads"))
    ok, checks = H.judge(H.readings(control, ref), bench.limits(workload))
    assert not ok, checks
