"""Record ``data/v5e_2x2_sharded.xplane.pb`` on a four-chip TPU v5e host
(run by hand there):

    python3 chipbench/tests/record_collectives.py <output .xplane.pb>

The program's sharded train step (``launch/steps.py:compile_sharded``) at
small widths with q/k/v bias on a (data=2, model=2) mesh, three steps
inside a ``chipbench.window`` span, each waited for."""
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

import jax                                                      # noqa: E402
import numpy as np                                              # noqa: E402

from chipbench import trace as T                                # noqa: E402
from repro.launch.mesh import make_mesh                         # noqa: E402
from repro.launch.steps import build_cell, compile_sharded      # noqa: E402
from repro.models import registry as R                          # noqa: E402
from repro.optim.adam import AdamConfig, init_opt_state         # noqa: E402

BATCH, SEQ = 4, 256


def program(mesh):
    """(configuration, train cell) of the recorded step over ``mesh``."""
    cfg = R.tiny_config("dense", d_model=512, num_heads=8, num_kv_heads=4,
                        d_ff=1024, vocab_size=1024, qkv_bias=True,
                        dtype="bfloat16")
    return cfg, build_cell(cfg, "train", SEQ, BATCH, mesh)


def main(out: str) -> int:
    assert jax.devices()[0].platform == "tpu", "record on a TPU"
    assert len(jax.devices()) >= 4, "record on a 2x2 host"
    mesh = make_mesh((2, 2), ("data", "model"), jax.devices()[:4])
    cfg, cell = program(mesh)
    step = compile_sharded(cell, mesh)
    at_params, at_opt, at_batch = step.in_shardings
    params = jax.jit(lambda k: R.init_model(k, cfg),
                     out_shardings=at_params)(jax.random.key(0))
    opt = jax.jit(lambda p: init_opt_state(p, AdamConfig()),
                  out_shardings=at_opt)(params)
    toks = np.arange(BATCH * SEQ, dtype=np.int32).reshape(BATCH, SEQ) \
        % cfg.vocab_size
    batch = jax.device_put({"tokens": toks, "labels": toks}, at_batch)
    params, opt, loss = step(params, opt, batch)
    float(loss)
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d, profiler_options=T.profile_options())
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for _ in range(3):
            params, opt, loss = step(params, opt, batch)
            float(loss)
    jax.profiler.stop_trace()
    shutil.copy(T.find_xspace(Path(d)), out)
    shutil.rmtree(d)
    r = T.reduce(Path(out), chips=4)
    print({k: r[k] for k in ("planes", "busy_s", "window_s", "top_ops")})
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
