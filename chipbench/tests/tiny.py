"""A copy of the benchmark's files with tiny configurations, for the CPU.

The configurations keep their families' structure at widths a CPU test can
hold; every changed key is listed in ``reduced``, so the harness builds
them from the registry as it builds the real ones."""
from __future__ import annotations

import copy
import json
import shutil
from pathlib import Path

from chipbench import harness as H

MAMBA = {"d_model": 64, "n_layer": 2, "vocab_size": 96, "d_state": 16,
         "headdim": 16, "expand": 2, "d_conv": 4, "chunk_size": 32,
         "dtype": "float32"}
QWEN = {"hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 96,
        "vocab_size": 96, "dtype": "float32"}


def _tiny(spec: dict, sizes: dict) -> dict:
    from repro import configs
    cfg = configs.get_config(spec["registry_id"])
    spec = copy.deepcopy(spec)
    for key, value in sizes.items():
        spec["reduced"][key] = getattr(cfg, spec["fields"][key])
        spec[key] = value
    return spec


#: The plain-step cell, which ``BENCHMARK.json`` leaves out until its
#: limits are set on the chip at CodeQwen1.5-7B's 4 kv heads; here it runs
#: at the tiny size under these limits.
PLAIN_CONFIG = {"name": "codeqwen1p5_7b_2l"}
PLAIN_CELL = {"name": "codeqwen7b_plain_step", "config": "codeqwen1p5_7b_2l",
              "traffic": "plain_b2x2048", "chips": 1, "why": "tiny"}
PLAIN_LIMITS = {"loss_gap": 4e-3, "grad_gap": 1.4e-3, "grad_diff_gap": 4.5e-2,
                "change_gap": 1.5e-2}


def make(tmp: Path, *, seq: int = 64, dtype: str = "float32") -> H.Bench:
    """``tmp`` gets the benchmark's drivers, metrics, references and limits,
    tiny configurations under the real names, traffic at ``seq``, and the
    plain-step cell."""
    bench_dir = tmp / "chipbench"
    shutil.copytree(H.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    manifest = H.load_json(H.ROOT / "BENCHMARK.json")
    manifest["configs"].append(PLAIN_CONFIG)
    manifest["workloads"].append(PLAIN_CELL)
    (bench_dir / "limits" / f"{PLAIN_CELL['name']}.json").write_text(
        json.dumps(PLAIN_LIMITS))
    for c in manifest["configs"]:
        spec = H.load_json(H.BENCH_DIR / "configs" / f"{c['name']}.json")
        sizes = dict(MAMBA if spec["reference"] == "mamba2" else QWEN,
                     dtype=dtype)
        (bench_dir / "configs" / f"{c['name']}.json").write_text(
            json.dumps(_tiny(spec, sizes)))
    for t in {w["traffic"] for w in manifest["workloads"]}:
        job = H.load_json(H.BENCH_DIR / "traffic" / f"{t}.json")
        job["seq"] = seq
        (bench_dir / "traffic" / f"{t}.json").write_text(json.dumps(job))
    return H.Bench(manifest, bench_dir)
