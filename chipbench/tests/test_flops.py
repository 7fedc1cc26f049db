"""Model operations per token against a hand reckoning of each layer."""
import pytest

from chipbench import flops
from chipbench import harness as H


def spec(name):
    return H.load_json(H.BENCH_DIR / "configs" / f"{name}.json")


def test_mamba2_2p7b_2l():
    # per layer and token, forward: in_proj 2*2560*(2*5120 + 2*128 + 80),
    # out_proj 2*5120*2560, conv 2*4*5376, SSD over a chunk of 256:
    # C.B 256*128 (one group, causal half) + 80 heads * (256*64 + 4*128*64);
    # head 2*2560*6285; training = 3x forward
    layer = (2 * 2560 * 10576 + 2 * 5120 * 2560 + 2 * 4 * 5376
             + 256 * 128 + 80 * (256 * 64 + 4 * 128 * 64))
    hand = 3 * (2 * layer + 2 * 2560 * 6285)
    got = flops.model_flops_per_token(spec("mamba2_2p7b_2l"), 1024)
    assert got == pytest.approx(hand, rel=1e-12)
    assert got == pytest.approx(0.63e9, rel=0.06)      # ~0.6 GFLOP a token


def test_codeqwen1p5_7b_2l():
    # per layer and token, forward: q, o 2*4096*4096 each, k, v 2*4096*512
    # each (4 kv heads of 128), attention 2 * 2 * 1024 * 4096 (causal half
    # of seq 2048), SwiGLU 3 * 2*4096*13440; head 2*4096*11552
    layer = (2 * 2 * 4096 * 4096 + 2 * 2 * 4096 * 512
             + 2 * 2 * 1024 * 4096 + 3 * 2 * 4096 * 13440)
    hand = 3 * (2 * layer + 2 * 4096 * 11552)
    got = flops.model_flops_per_token(spec("codeqwen1p5_7b_2l"), 2048)
    assert got == pytest.approx(hand, rel=1e-12)
    assert got == pytest.approx(2.82e9, rel=0.01)


def test_ssd_scan_kernel_is_memory_bound_on_v5e():
    ops, nbytes = flops.ssd_scan_per_token_layer(spec("mamba2_2p7b_2l"), 1024)
    peaks = H.load_json(H.BENCH_DIR / "peaks.json")["TPU v5 lite"]
    assert ops == 80 * (2 * 256 * 128 + 2 * 256 * 64 + 4 * 128 * 64)
    assert nbytes == 80 * (4 * 64 + 8 + 2 * 128 * 2 + 64 * 2)
    assert nbytes / peaks["hbm_bytes_per_s"] > ops / peaks["bf16_flops_per_s"]
