"""The harness without a chip: the peaks table, the refusal to run off a
TPU, and that a later cell is found from files and a ``BENCHMARK.json``
entry alone."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from cells import BENCH, ROOT, run

import peaks


def test_known_device_has_its_peaks():
    p = peaks.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["source"]


def test_unknown_device_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("cpu")


def test_run_off_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "mimic3_fig8.fit", "--seed", "3", "--seconds", "1", "--trace",
         "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_a_new_cell_is_found_from_files_alone(tmp_path):
    """Copy the benchmark, then add a configuration, a traffic mix, a
    limits file and a per-layer metric as new files plus one entry each in
    BENCHMARK.json: the harness finds all of them by name."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((BENCH / "configs" / "mimic3_fig8.json").read_text())
    cfg.update(name="credit_fig8", dataset={"name": "credit", "n": 20000,
                                            "d": 23, "n_classes": 2})
    (tmp_path / "bench" / "configs" / "credit_fig8.json").write_text(
        json.dumps(cfg))
    traffic = json.loads((BENCH / "traffic" / "fit.json").read_text())
    traffic["seed_lanes_per_fit"] = 3
    (tmp_path / "bench" / "traffic" / "fit3.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench" / "limits" / "credit_fig8.fit3.json").write_text(
        json.dumps({"limits": {"g1_epoch1": 0.05}}))
    (tmp_path / "bench" / "metrics" / "lanes_per_fit.fit3.py").write_text(
        "def read(ctx):\n    return ctx['traffic']['seed_lanes_per_fit']\n")
    bench["configs"].append({"name": "credit_fig8", "source": "x",
                             "file": "bench/configs/credit_fig8.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "credit_fig8.fit3",
                               "config": "credit_fig8", "traffic": "fit3",
                               "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "lanes_per_fit.fit3", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "protocol", "moves": "fit_s",
                               "workloads": ["credit_fig8.fit3"]})
    for m in bench["end_to_end"]:
        if m["name"] == "fit_s":
            m["workloads"].append("credit_fig8.fit3")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    spec = run.load_cell("credit_fig8.fit3", root=tmp_path)
    assert spec["config"]["dataset"]["name"] == "credit"
    assert spec["traffic"]["seed_lanes_per_fit"] == 3
    assert spec["limits"] == {"g1_epoch1": 0.05}
    assert [m["name"] for m in spec["per_layer"]] == ["lanes_per_fit.fit3"]
    assert {m["name"] for m in spec["end_to_end"]} == {"fit_s", "setup_s"}
    values = run.per_layer_values(spec, {"traffic": spec["traffic"]})
    assert values == {"lanes_per_fit.fit3": {"value": 3, "unit": "1"}}
    runner = run.load_module(tmp_path / "bench" / "runners"
                             / f"{spec['traffic']['runner']}.py")
    assert hasattr(runner, "Cell")


def test_every_cell_names_existing_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    per_layer = {m["name"] for m in bench["per_layer"]}
    for w in bench["workloads"]:
        spec = run.load_cell(w["name"])
        assert (BENCH / "runners"
                / f"{spec['traffic']['runner']}.py").exists()
        assert spec["limits"]
    for name in per_layer:
        assert (BENCH / "metrics" / f"{name}.py").exists(), name
