"""Sharding rules + a reduced-mesh lowering test (the in-process twin of the
512-device dry-run, kept cheap for CI: 8 placeholder devices via subprocess).
"""
import json
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.models import registry as R
from repro.parallel import sharding as S


class FakeMesh:
    """Just enough of a Mesh for the rule functions."""
    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


class TestFit:
    def test_divisible(self):
        m = FakeMesh({"data": 16, "model": 16})
        assert S._fit(m, 64, "model") == "model"
        assert S._fit(m, 63, "model") is None

    def test_suffix_fallback(self):
        m = FakeMesh({"pod": 2, "data": 16, "model": 16})
        # 16 divides by ("data",) but not ("pod","data")=32
        assert S._fit(m, 16, ("pod", "data")) == "data"
        assert S._fit(m, 64, ("pod", "data")) == ("pod", "data")

    def test_odd_vocab_unsharded(self):
        m = FakeMesh({"data": 16, "model": 16})
        # whisper vocab 51865 is odd -> cannot shard on 16
        assert S._fit(m, 51865, "model") is None


class TestParamSpecs:
    def test_rules_cover_all_leaves(self):
        m = FakeMesh({"data": 16, "model": 16})
        for fam in ("dense", "moe", "ssm", "hybrid"):
            cfg = R.tiny_config(fam)
            shapes = R.model_param_shapes(cfg)
            specs = S.param_pspecs(cfg, m, shapes)
            # same tree structure, all PartitionSpec
            leaves = jax.tree.leaves(specs,
                                     is_leaf=lambda x: isinstance(x, P))
            assert all(isinstance(s, P) for s in leaves)
            n_shapes = len(jax.tree.leaves(shapes))
            assert len(leaves) == n_shapes

    def test_no_duplicate_axis_in_spec(self):
        m = FakeMesh({"pod": 2, "data": 16, "model": 16})
        for fam in ("dense", "moe", "hybrid"):
            cfg = R.tiny_config(fam)
            shapes = R.model_param_shapes(cfg)
            specs = S.param_pspecs(cfg, m, shapes)
            for s in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)):
                used = []
                for entry in s:
                    if entry is None:
                        continue
                    names = (entry,) if isinstance(entry, str) else entry
                    used.extend(names)
                assert len(used) == len(set(used)), s


LOWER_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.launch.steps import build_cell
from repro.parallel.sharding import to_shardings
from repro.models import registry as R

cfg = R.tiny_config("{family}")
mesh = make_mesh((2, 4), ("data", "model"))
cell = build_cell(cfg, "{shape}", seq={seq}, batch=4, mesh=mesh, remat=False)
in_sh = tuple(to_shardings(mesh, p) for p in cell.arg_pspecs)
out_sh = to_shardings(mesh, cell.out_pspecs)
with mesh:
    lowered = jax.jit(cell.fn, in_shardings=in_sh, out_shardings=out_sh,
                      donate_argnums=cell.donate).lower(*cell.arg_shapes)
    compiled = lowered.compile()
print(json.dumps({{"ok": True, "flops": compiled.cost_analysis()["flops"]}}))
"""


@pytest.mark.parametrize("family,shape,seq", [
    ("dense", "train_4k", 64),
    ("moe", "train_4k", 64),
    ("ssm", "train_4k", 64),
    ("hybrid", "decode_32k", 64),
    ("dense", "prefill_32k", 64),
])
def test_reduced_mesh_lowering(family, shape, seq):
    """lower+compile on an 8-device (2x4) mesh in a subprocess (device count
    must be set before jax init)."""
    script = LOWER_SCRIPT.format(family=family, shape=shape, seq=seq)
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=600,
                         env={**__import__("os").environ,
                              "PYTHONPATH": "src"},
                         cwd=str(__import__("pathlib").Path(__file__).parents[1]))
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["ok"] and res["flops"] > 0
