"""``chip_smoke.py``'s phases on the CPU at a tiny size, kernels interpreted.

The script's device check lives in ``main()`` only, so the phases run here;
``main()`` itself must refuse the CPU, and so must the script alone in a
directory without the repository.
"""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.compile_cache import CHECKOUT_CACHE, enable_compile_cache
from repro.core.cluster import VirtualCluster
from repro.kernels.check import kernel_cases, width_cases
from repro.models import registry as R

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
import chip_smoke  # noqa: E402


def _tiny_bf16():
    return R.tiny_config("dense", dtype="bfloat16")


def test_cut_keeps_published_widths():
    cfg = chip_smoke.codeqwen_cut()
    assert (cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.d_ff, cfg.rope_theta, cfg.dtype) == \
        (4096, 32, 32, 128, 13440, 1e6, "bfloat16")
    assert (cfg.num_layers, cfg.vocab_size) == (2, 11552)


def test_kernels_phase(capsys):
    cases = kernel_cases(0) + width_cases(
        0, flash=(1, 128, 2, 128), rmsnorm=(16, 256),
        ssd=(1, 256, 2, 64, 128, 128), adam_n=3000)
    assert chip_smoke.kernels_phase(cases)
    out = capsys.readouterr().out
    assert out.count("PASS") == len(cases) and "FAIL" not in out


def test_reference_loss_matches_the_cluster_in_float32():
    """The reference restacks the cluster's per-layer params into the
    scan layout; in float32 it must reproduce the cluster's step-0 loss."""
    cl = VirtualCluster(R.tiny_config("dense"), dp=2, pp=2, global_batch=2,
                        num_micro=1, seq_len=16, seed=3)
    ref = chip_smoke.reference_loss(cl)
    assert ref == pytest.approx(cl.train_step(), rel=1e-5)


def test_elastic_phase(capsys):
    assert chip_smoke.elastic_phase(_tiny_bf16(), seed=0, seq=16,
                                    expect_mosaic=False)
    out = capsys.readouterr().out
    assert "recovery measured" in out and "modeled" in out
    assert "recompile after recovery" in out
    assert out.count("[check]") == 3 and "FAIL" not in out


def test_main_refuses_the_cpu(capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_script_alone_fails(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = {"PYTHONPATH": ""}
    for cwd in (ROOT, tmp_path):
        proc = _run(["chip_smoke.py"], cwd, env)
        assert proc.returncode != 0
        assert '"ok"' not in proc.stdout


SHARDED = """
import sys
sys.path.insert(0, {root!r})
import chip_smoke
from repro.models import registry as R
cfg = R.tiny_config("dense", dtype="bfloat16", num_kv_heads=4)
sys.exit(0 if chip_smoke.sharded_phase(cfg, seed=0, seq=16) else 1)
"""


def test_sharded_phase_on_four_cpu_devices():
    """The --chips 4 path on four virtual CPU devices (the device count is
    fixed at jax start-up, hence the subprocess)."""
    proc = _run(["-c", SHARDED.format(root=str(ROOT))], ROOT, {
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": str(ROOT / "src")})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "sharded == unsharded" in proc.stdout
    assert proc.stdout.count("bytes_in_use=") == 5      # 4 sharded + 1


def test_compile_cache_location(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(CHECKOUT_CACHE)
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE)
        assert CHECKOUT_CACHE == ROOT / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

