"""A configuration, a traffic mix and a metric reader that a later change
adds as files are found by name, with no edit of the harness; a
configuration whose published width disagrees with the registry is
refused."""
import json

import pytest

from chipbench import harness as H
from chipbench.tests import tiny


def test_new_files_are_found_by_name(tmp_path):
    bench = tiny.make(tmp_path)
    d = bench.bench_dir
    spec = json.loads((d / "configs" / "mamba2_2p7b_2l.json").read_text())
    spec["name"] = "mamba2_2p7b_4l"
    spec["n_layer"] = 4
    (d / "configs" / "mamba2_2p7b_4l.json").write_text(json.dumps(spec))
    job = H.load_json(d / "traffic" / "elastic_steady.json")
    job["global_batch"] = 4
    (d / "traffic" / "elastic_gb4.json").write_text(json.dumps(job))
    (d / "metrics" / "steps_in_window.py").write_text(
        "def read(ctx):\n    return float(len(ctx.window['steps']))\n")
    bench.manifest["configs"].append({"name": "mamba2_2p7b_4l"})
    bench.manifest["workloads"].append(
        {"name": "mamba2_4l_gb4", "config": "mamba2_2p7b_4l",
         "traffic": "elastic_gb4", "chips": 1, "why": "test"})
    bench.manifest["per_layer"].append(
        {"name": "steps_in_window", "unit": "steps", "better": "higher",
         "source": "host_clock", "layer": "model step",
         "moves": "tokens_per_s.elastic", "workloads": ["mamba2_4l_gb4"]})

    w = bench.workload("mamba2_4l_gb4")
    cfg = H.model_config(bench.config(w["config"]))
    assert cfg.num_layers == 4 and cfg.d_model == tiny.MAMBA["d_model"]
    assert bench.traffic(w["traffic"])["global_batch"] == 4
    assert bench.driver(bench.traffic(w["traffic"])["driver"]).Run
    names = [m["name"] for m in bench.metrics_for("mamba2_4l_gb4", True)]
    assert "steps_in_window" in names
    ctx = type("Ctx", (), {"window": {"steps": [{}, {}, {}]}})
    assert bench.reader("steps_in_window").read(ctx) == 3.0


def test_a_changed_published_width_is_refused():
    spec = H.load_json(H.BENCH_DIR / "configs" / "mamba2_2p7b_2l.json")
    H.model_config(spec)                        # as committed: accepted
    spec["d_state"] = 64                        # a width, not in reduced
    with pytest.raises(ValueError, match="d_state"):
        H.model_config(spec)
    spec = H.load_json(H.BENCH_DIR / "configs" / "codeqwen1p5_7b_2l.json")
    spec["reduced"]["num_hidden_layers"] = 40   # cut from a wrong source
    with pytest.raises(ValueError, match="num_hidden_layers"):
        H.model_config(spec)


def test_a_declared_registry_departure_runs_the_source_value():
    """CodeQwen1.5-7B has 4 kv heads; the registry's 32 is declared in
    ``registry_departs`` and the file's value is what runs.  Undeclared,
    the same disagreement is refused."""
    spec = H.load_json(H.BENCH_DIR / "configs" / "codeqwen1p5_7b_2l.json")
    assert H.model_config(spec).num_kv_heads == 4 == spec["num_key_value_heads"]
    del spec["registry_departs"]["num_key_value_heads"]
    with pytest.raises(ValueError, match="num_key_value_heads"):
        H.model_config(spec)


def test_every_manifest_name_has_its_files():
    bench = H.Bench.load()
    for w in bench.manifest["workloads"]:
        H.model_config(bench.config(w["config"]))
        job = bench.traffic(w["traffic"])
        assert bench.driver(job["driver"]).Run
        assert set(bench.limits(w["name"])) == {"loss_gap", "grad_gap",
                                               "grad_diff_gap", "change_gap"}
    for m in bench.manifest["end_to_end"] + bench.manifest["per_layer"]:
        assert callable(bench.reader(m["name"]).read)
