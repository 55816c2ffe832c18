"""Guard rails of the harness: no result off a TPU, no default for an
unknown chip, names and units as the contract allows them, and a cell
added as files only is found by name."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import generate
from bench import run as R

ROOT = Path(__file__).resolve().parents[2]


def _bench(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_off_tpu_exits_nonzero_without_result():
    p = _bench(ROOT, "--workload", "local-batch", "--seed", "1",
               "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    """A directory with BENCHMARK.json and bench/ only: no program."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench(tmp_path, "--workload", "global-served", "--seed", "1",
               "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_unknown_device_kind_is_an_error():
    assert R.peak_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        R.peak_of("TPU v99")


def test_benchmark_json_keeps_the_contract():
    spec = R.load_spec()
    keys = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    assert set(spec) == keys
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for cell in spec["workloads"]:
        name = cell["name"]
        assert cell["chips"] == 1
        assert len(cell["why"]) <= 200
        assert [m for m in R.metrics_for(spec, name, False)
                if m["name"] != "setup_s"]
        assert R.metrics_for(spec, name, True)
        R.load_config(cell["config"])
        generate.load_mix(R.BENCH, cell["traffic"])
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        R.load_reader(m["name"])
        for w in m["workloads"]:
            assert m["moves"] in {x["name"] for x in
                                  R.metrics_for(spec, w, False)}
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        R.load_reader(m["name"])


@pytest.mark.parametrize("field,bad", [("name", "has space"),
                                       ("name", "a/b"), ("unit", "µs"),
                                       ("unit", "tokens per second")])
def test_bad_names_and_units_are_refused(field, bad):
    spec = R.load_spec()
    spec["per_layer"][0][field] = bad
    with pytest.raises(ValueError):
        R.validate(spec)


def test_duplicate_cell_is_refused():
    spec = R.load_spec()
    spec["workloads"].append(dict(spec["workloads"][0]))
    with pytest.raises(ValueError):
        R.validate(spec)


def test_new_cell_is_found_by_name(tmp_path):
    """A cell, a traffic mix and a metric added as files, in a copy, with
    no edit to the harness."""
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = R.load_spec()
    spec["workloads"].append({"name": "throwaway", "config":
                              "aidw1000k-knn15", "traffic": "throwaway-mix",
                              "chips": 1, "why": "found by name"})
    spec["per_layer"].append({"name": "throwaway_metric", "unit": "ms",
                              "better": "lower", "source": "host_clock",
                              "layer": "device", "moves": "latency_p50_ms",
                              "workloads": ["throwaway"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    mix = dict(generate.load_mix(R.BENCH, "churn-64-1024"), rate_per_s=3.0)
    (bench / "traffic" / "throwaway-mix.json").write_text(json.dumps(mix))
    (bench / "metrics" / "throwaway_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    got = R.load_spec(tmp_path)
    cell = R.find(got["workloads"], "throwaway")
    assert generate.load_mix(bench, cell["traffic"])["rate_per_s"] == 3.0
    assert R.load_config(cell["config"], bench)["k"] == 15
    names = [m["name"] for m in R.metrics_for(got, "throwaway", True)]
    assert names == ["throwaway_metric"]
    assert R.load_reader("throwaway_metric", bench)(None) == 42.0
