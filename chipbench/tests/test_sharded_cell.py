"""The four-chip cell ``codeqwen7b_8l_plain_2x2`` at a size the CPU holds,
on four virtual CPU devices (fixed at jax start-up, hence one subprocess
for every case): the sound sharded step passes its limits against
``reference/qwen2_bias.py``; its weights, made sharded, equal
``weights.make``'s unsharded ones bit for bit; and a step with a fault
planted under its timed path fails them.

The faults: the state returned unchanged, half of the batch left out (the
mean taken over the rest), the answer altered by one bf16 unit where the
step returns it, and the data axis's gradient reduction left out, so that
each data replica steps the shard of the state it holds on its own half's
gradient (and a leaf held whole on every replica on replica 0's).
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
WORKLOAD = "codeqwen7b_8l_plain_2x2"
FAULTS = ["state_unchanged", "half_batch", "answer_altered",
          "no_data_reduction"]

SCRIPT = r'''
import json, sys, tempfile
from pathlib import Path
sys.path[:0] = [{root!r}, {src!r}]
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from chipbench import harness as H, run as R, weights
from chipbench.reference.common import named_leaves
from chipbench.tests import tiny
from repro.launch import steps as S
from repro.models import registry as Reg

W, SEED, UNIT = {workload!r}, 2 ** 31 + 77, 2.0 ** -7
bench = tiny.make(Path(tempfile.mkdtemp()))
out = {{}}


def plant(fault):
    if fault == "state_unchanged":
        S.adam_update = lambda params, grads, state, cfg: (params, state)
        return
    if fault == "half_batch":
        orig = Reg.make_train_loss

        def half(cfg, **kw):
            f = orig(cfg, **kw)
            return lambda p, b, rng_ctx=None: f(
                p, {{k: v[:v.shape[0] // 2] for k, v in b.items()}}, rng_ctx)
        S.R.make_train_loss = half
        return
    orig_cell = S.build_cell

    def cell(cfg, shape, seq, batch, mesh, adam=None, remat=True):
        c = orig_cell(cfg, shape, seq, batch, mesh, adam=adam, remat=remat)
        fn, specs = c.fn, c.arg_pspecs[0]
        loss_fn = Reg.make_train_loss(cfg, remat=remat)

        def own_half(spec, g0, g1):
            dims = [d for d, a in enumerate(spec)
                    if a == "data" or (isinstance(a, tuple) and "data" in a)]
            if not dims:
                return g0
            d, n = dims[0], g0.shape[dims[0]] // 2
            return jnp.concatenate([jax.lax.slice_in_dim(g0, 0, n, axis=d),
                                    jax.lax.slice_in_dim(g1, n, 2 * n, axis=d)],
                                   axis=d)

        def step(params, opt, b):
            if fault == "answer_altered":
                p, o, loss = fn(params, opt, b)
                return p, o, loss * (1 + UNIT)
            h = b["tokens"].shape[0] // 2
            (l0, g0), (l1, g1) = [jax.value_and_grad(loss_fn)(
                params, {{k: v[i * h:(i + 1) * h] for k, v in b.items()}})
                for i in (0, 1)]
            grads = jax.tree.map(own_half, specs, g0, g1,
                                 is_leaf=lambda x: isinstance(x, P))
            p, o = S.adam_update(params, grads, opt, adam)
            return p, o, (l0 + l1) / 2
        c.fn = step
        return c
    S.build_cell = cell


fault = sys.argv[1]
if fault == "weights":
    ctx = R.make_context(bench, W, SEED, H.Spans())
    driver = bench.driver(ctx.traffic["driver"]).Run(ctx)
    driver.setup()
    made = named_leaves(driver.init())
    whole = named_leaves(jax.device_get(weights.make(
        ctx.reference.param_shapes(ctx.spec), SEED)))
    out["same_bits"] = sorted(made) == sorted(whole) and all(
        np.array_equal(np.asarray(made[k]), whole[k]) for k in whole)
    out["spread"] = sorted(k for k, a in made.items()
                           if len(a.sharding.device_set) == 4
                           and not a.sharding.is_fully_replicated)
elif fault == "calibrate":
    from chipbench.calibrate_sharded import seed_readings
    out = seed_readings(bench, W, SEED)
else:
    if fault != "sound":
        plant(fault)
    r = R.run(bench, W, SEED, 0.3, trace=False, check_device=False)
    out = {{"correct": r["correct"], "checks": r["checks"],
           "count": r["device"]["count"]}}
print("RESULT " + json.dumps(out))
'''


def _case(case):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SCRIPT.format(root=str(ROOT), src=str(ROOT / "src"),
                         workload=WORKLOAD)
    proc = subprocess.run([sys.executable, "-c", code, case], env=env,
                          cwd=ROOT, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_sound_sharded_step_is_correct():
    r = _case("sound")
    assert r["count"] == 4
    assert r["correct"], r["checks"]


def test_sharded_weights_equal_the_unsharded_ones_bit_for_bit():
    r = _case("weights")
    assert r["same_bits"]
    # the matrices and biases are spread over the four chips
    assert {"embed/embedding", "head/w", "segments/0/0/attn/wq",
            "segments/0/0/attn/bk", "segments/0/0/mlp/wo"} <= set(r["spread"])


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_is_not_correct(fault):
    r = _case(fault)
    assert not r["correct"], (fault, r["checks"])


def test_calibration_judges_each_variant_by_the_cells_limits():
    """``calibrate_sharded.py``'s readings over the set-up steps, with the
    first gradients compared on the host: half of each batch is not
    correct by the cell's limits, and each variant carries its verdict."""
    r = _case("calibrate")
    assert r["steps"] == 3
    for kind in ("control", "half_batch"):
        assert set(r[kind]) == {"loss_gap", "grad_gap", "grad_diff_gap",
                                "change_gap", "correct"}, r[kind]
    assert r["half_batch"]["correct"] is False, r["half_batch"]
