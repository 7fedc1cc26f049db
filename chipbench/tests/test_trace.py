"""The reduction from a trace to busy time, ops and labelled idle gaps."""
from pathlib import Path

import pytest

from chipbench import trace as T

DATA = Path(__file__).parent / "data"


def test_union_clip_and_gaps():
    ms = 1_000_000
    ops = {0: [("fusion.1", 0 * ms, 30 * ms), ("fusion.1", 20 * ms, 40 * ms),
               ("%jit_ssd_scan.3 = f32[8] custom-call(%p), "
                'custom_call_target="tpu_custom_call"', 60 * ms, 70 * ms),
               ("fusion.2", 95 * ms, 130 * ms)],     # runs past the window
           1: [("fusion.1", 10 * ms, 20 * ms)]}       # a chip not used
    host = [("chipbench.window", 10 * ms, 100 * ms),
            ("chipbench.train_step", 10 * ms, 100 * ms),
            ("chipbench.detect_and_recover", 42 * ms, 58 * ms),
            ("other", 0, 200 * ms)]
    r = T.reduce_events(ops, host, (10 * ms, 100 * ms), chips=1)
    assert r["window_s"] == pytest.approx(0.090)
    assert r["busy_s"] == pytest.approx(0.030 + 0.010 + 0.005)
    assert r["ops"]["fusion.1"] == [pytest.approx(0.040), 2]
    assert r["ops"]["fusion.2"] == [pytest.approx(0.005), 1]
    assert r["top_ops"][0][0] == "fusion.1"
    assert r["kernels"] == {"jit_ssd_scan.3": [pytest.approx(0.010), 1]}
    # gaps: 40-60 (inside detect_and_recover at its middle), 70-95
    assert r["idle_gaps"] == [["train_step", pytest.approx(0.025)],
                              ["detect_and_recover", pytest.approx(0.020)]]


def test_busy_averages_over_the_chips_used():
    ops = {0: [("a", 0, 50)], 1: [("a", 0, 100)]}
    r = T.reduce_events(ops, [], (0, 100), chips=2)
    assert r["busy_s"] == pytest.approx(75e-9)
    assert r["ops"]["a"] == [pytest.approx(75e-9), 2]


@pytest.mark.skipif(not (DATA / "v5e_small.xplane.pb").exists(),
                    reason="no recorded chip trace")
def test_recorded_chip_trace():
    """A trace recorded on one TPU v5e chip: a jitted matmul and a Pallas
    RMSNorm, five times each, inside a ``chipbench.window`` span, with a
    ``chipbench.host_sleep`` span of 50 ms between them."""
    r = T.reduce(DATA / "v5e_small.xplane.pb", chips=1)
    assert r["planes"] == [0]
    assert 0 < r["busy_s"] < r["window_s"]
    assert [n for n in r["kernels"] if "rmsnorm" in n]
    assert sum(c for _, c in r["kernels"].values()) == 5
    assert r["idle_gaps"][0][0] == "host_sleep"
    assert r["idle_gaps"][0][1] >= 0.045
