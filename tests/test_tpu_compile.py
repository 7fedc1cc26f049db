"""The Pallas kernels compile with Mosaic for a TPU v5e at the training
path's widths, without a chip: each is compiled for one chip of a described
``v5e:2x2`` topology.  The interpreter hides tiling and lowering faults
(the SSD scan's ``dt`` block, ``cumsum``, a dynamic VMEM index); Mosaic
refuses them here.

The topology is described inside a fixture only: one process at a time may
load the TPU library, so describing it at import would break every other
test worker.  Keep all chip-compile tests in this one file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch, one_chip):
    """Shapes on the described chip, with the kernels lowered by Mosaic: the
    process's default backend is the CPU, so ``ops.interpret_mode`` would
    pick the interpreter."""
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    jax.clear_caches()          # drop traces made with the interpreter
    yield lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    jax.clear_caches()


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_flash_attention_value_and_grad(mosaic):
    """CodeQwen1.5-7B heads at seq 2048.  ``value_and_grad``, not ``grad``:
    the custom VJP's backward is the jnp oracle, so a grad-only program
    drops the Pallas forward as dead code."""
    qkv = [mosaic((1, 2048, 32, 128), jnp.bfloat16)] * 3

    def loss(q, k, v):
        return ops.flash_attention(q, k, v).astype(jnp.float32).sum()

    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 2)), *qkv)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_rmsnorm(mosaic, dtype):
    """f32 too: its row blocks must fit the scoped VMEM (256 rows of 4096
    in f32 did not)."""
    text = _compiled_text(lambda x, s: ops.rmsnorm(x, s),
                          mosaic((2048, 4096), dtype), mosaic((4096,)))
    assert "tpu_custom_call" in text


def test_ssd_scan_mamba2_widths(mosaic):
    """mamba2-2.7b: 80 heads of 64, state 128, one group, chunk 256."""
    b, s, h, p, n = 1, 2048, 80, 64, 128
    text = _compiled_text(
        lambda x, dt, A, B, C: ops.ssd_scan(x, dt, A, B, C, chunk=256)[0],
        mosaic((b, s, h, p)), mosaic((b, s, h)), mosaic((h,)),
        mosaic((b, s, 1, n)), mosaic((b, s, 1, n)))
    assert "tpu_custom_call" in text


def test_fused_adam(mosaic):
    n = 4 * 1024 * 1024
    text = _compiled_text(
        lambda g, m, mu, nu: ops.fused_adam(g, m, mu, nu, step=3),
        *[mosaic((n,))] * 4)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("chunk", [8, 256])
def test_ssd_scan_corpus_chunks(mosaic, chunk):
    """``kernels/check.py``'s corpus runs the scan in chunks of 8: blocks
    narrower than a 128-lane tile must still be legal."""
    b, s, h, p, g, n = 2, 256, 4, 16, 2, 16
    text = _compiled_text(
        lambda x, dt, A, B, C: ops.ssd_scan(x, dt, A, B, C, chunk=chunk)[0],
        mosaic((b, s, h, p)), mosaic((b, s, h)), mosaic((h,)),
        mosaic((b, s, g, n)), mosaic((b, s, g, n)))
    assert "tpu_custom_call" in text


def test_sharded_step_collective_bytes_match_the_recorded_trace(topo):
    """The sharded train step recorded on a 2x2 v5e host
    (``chipbench/tests/record_collectives.py``), compiled here by
    ``compile_sharded`` for the described 2x2: the ``collective_bytes`` its
    spans carry (a step's, its layer loop's trips read from the loop's
    condition) are the bytes every chip's op line started a step in the
    recording (``chipbench/tests/test_collectives.py``)."""
    import numpy as np
    from jax.sharding import Mesh

    from chipbench.tests.record_collectives import program
    from repro.launch.steps import compile_sharded

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    _, cell = program(mesh)
    assert compile_sharded(cell, mesh).collective_bytes == 45_230_576
