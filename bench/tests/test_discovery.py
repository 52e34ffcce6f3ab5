"""A cell, a configuration and a layer metric added as files alone are found
by name, and BENCHMARK.json keeps to the benchmark's rules."""
import copy
import importlib.util
import json
import os
import shutil
import sys

import pytest

from bench.peaks import peaks_for
from bench.spec_check import problems

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def test_benchmark_json_keeps_the_rules(spec):
    assert problems(spec, ROOT) == []


@pytest.mark.parametrize("breach, expect", [
    (lambda s: s["end_to_end"][0].update(name="build s"), "bad name"),
    (lambda s: s["end_to_end"][0].update(unit="seconds per build"),
     "bad unit"),
    (lambda s: s["per_layer"][0].update(workloads=["gist1m-search"]),
     "does not report"),
    (lambda s: s["per_layer"][0].update(name="builder.nothing_ms"),
     "no reader"),
    (lambda s: s["workloads"][0].update(why="x" * 201), "one line"),
    (lambda s: s["end_to_end"][0].update(bound=0.3), "bound"),
])
def test_breaches_are_found(spec, breach, expect):
    bad = copy.deepcopy(spec)
    breach(bad)
    assert any(expect in p for p in problems(bad, ROOT))


def _copy_of_bench(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    spec = importlib.util.spec_from_file_location(
        "copied_bench_run", tmp_path / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    saved = list(sys.path)
    try:
        spec.loader.exec_module(mod)
    finally:
        sys.path[:] = saved
    return mod


def test_new_cell_config_and_metric_found_by_name(tmp_path, spec):
    run = _copy_of_bench(tmp_path)
    bench = tmp_path / "bench"
    cfg = json.loads((bench / "configs" / "sift1m.json").read_text())
    cfg.update(name="sift_half", rows=500000)
    (bench / "configs" / "sift_half.json").write_text(json.dumps(cfg))
    cell = json.loads((bench / "workloads" / "sift1m-build.json").read_text())
    cell.update(config="sift_half", traffic="build_long_cycles")
    cell["params"]["cycle"] = ["sweep"] * 8 + ["reverse"]
    (bench / "workloads" / "sift_half-build.json").write_text(
        json.dumps(cell))
    (bench / "layer_metrics" / "builder.calls.py").write_text(
        "def read(ctx):\n"
        "    c = ctx['counters']\n"
        "    return c['sweeps'] + c['reverses']\n")
    spec = copy.deepcopy(spec)
    spec["configs"].append({"name": "sift_half", "source": "a source",
                            "file": "bench/configs/sift_half.json",
                            "reduced": ["rows"], "why": "a test"})
    spec["workloads"].append({"name": "sift_half-build",
                              "config": "sift_half",
                              "traffic": "build_long_cycles", "chips": 1,
                              "why": "a test"})
    spec["end_to_end"][0]["workloads"].append("sift_half-build")
    spec["per_layer"].append({"name": "builder.calls", "unit": "calls",
                              "better": "higher", "source": "host_clock",
                              "layer": "builder (core.rnn_descent, "
                                       "core.graph)",
                              "moves": "build_s",
                              "workloads": ["sift_half-build"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    assert problems(spec, str(tmp_path)) == []
    entry, cell_f, cfg_f = run.load_cell("sift_half-build")
    assert entry["traffic"] == "build_long_cycles"
    assert cfg_f["rows"] == 500000 and len(cell_f["params"]["cycle"]) == 9
    assert [m["name"] for m in run.metrics_for(spec, "sift_half-build",
                                                False)] == ["build_s",
                                                            "setup_s"]
    assert [m["name"] for m in run.metrics_for(spec, "sift_half-build",
                                                True)] == ["builder.calls"]
    reader = run._load_module("layer_metrics", "builder.calls")
    assert reader.read({"counters": {"sweeps": 8, "reverses": 1}}) == 9
    driver = run._load_module("traffic", cell_f["driver"])
    assert all(hasattr(driver, f) for f in ("setup", "window", "verify"))


def test_every_named_file_loads(spec):
    from bench import run

    for w in spec["workloads"]:
        _, cell, _ = run.load_cell(w["name"], spec)
        driver = run._load_module("traffic", cell["driver"])
        assert all(hasattr(driver, f) for f in ("setup", "window", "verify"))
    for m in spec["per_layer"]:
        assert callable(run._load_module("layer_metrics", m["name"]).read)


def test_peaks_by_device_kind():
    p = peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
