"""Record ``data/v5e_small.xplane.pb`` on one TPU chip (run by hand there):

    python3 chipbench/tests/record_trace.py <output .xplane.pb>

A jitted matmul and the program's Pallas RMSNorm, five times each, inside a
``chipbench.window`` span, with a 50 ms ``chipbench.host_sleep`` span
between them in which the chip runs nothing."""
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402

from chipbench import trace as T                                # noqa: E402
from repro.kernels import ops                                   # noqa: E402

def main(out: str) -> int:
    assert jax.devices()[0].platform == "tpu", "record on a TPU"
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    s = jnp.ones((2048,), jnp.float32)
    mm = jax.jit(lambda a: a @ a)
    mm(x).block_until_ready()
    ops.rmsnorm(x, s).block_until_ready()
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d, profiler_options=T.profile_options())
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for _ in range(5):
            mm(x).block_until_ready()
        with jax.profiler.TraceAnnotation("chipbench.host_sleep"):
            time.sleep(0.05)
        for _ in range(5):
            ops.rmsnorm(x, s).block_until_ready()
    jax.profiler.stop_trace()
    shutil.copy(T.find_xspace(Path(d)), out)
    shutil.rmtree(d)
    print(T.reduce(Path(out), chips=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
