"""BENCHMARK.json against the files that the harness finds by name, the
peak table, and the refusal to run without a TPU."""
import importlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, peaks  # noqa: E402

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in MANIFEST["workloads"]]
NAME = r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$"


def _reports(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files(cell):
    w = next(w for w in MANIFEST["workloads"] if w["name"] == cell)
    c = harness.load_cell(cell)
    assert c.spec["config"] == w["config"]
    assert c.spec["traffic_name"] == w["traffic"]
    assert w["config"] in {k["name"] for k in MANIFEST["configs"]}
    assert set(c.spec["limits"]) == {"loss_gap", "grad_gap", "update_gap"}
    for fn in ("init_weights", "ref_loss", "loss_tokens", "step_flops",
               "kernel_work"):
        assert callable(getattr(c.mod, fn))
    assert w["chips"] == 1


@pytest.mark.parametrize("config", [c["name"] for c in MANIFEST["configs"]])
def test_config_files(config):
    entry = next(c for c in MANIFEST["configs"] if c["name"] == config)
    conf = json.loads((ROOT / entry["file"]).read_text())
    assert conf["name"] == config
    assert conf["source"] == entry["source"]
    assert conf["reduced"] == entry["reduced"]
    assert "assumed" in conf and "sizes" in conf
    assert (ROOT / entry["file"]).with_suffix(".py").exists()
    assert any(w["config"] == config for w in MANIFEST["workloads"])


@pytest.mark.parametrize("metric", [m["name"] for m in MANIFEST["per_layer"]])
def test_per_layer_metric(metric):
    m = next(m for m in MANIFEST["per_layer"] if m["name"] == metric)
    reader = importlib.import_module(f"bench.metrics.{metric}")
    assert callable(reader.read)
    moved = next(e for e in MANIFEST["end_to_end"] if e["name"] == m["moves"])
    cells = m.get("workloads", CELLS)
    assert cells and set(cells) <= set(CELLS)
    assert all(_reports(moved, c) for c in cells)
    if metric.endswith("_roofline"):
        assert m["unit"] == "%"
        kernel = metric[:-len("_roofline")]
        assert all(kernel in harness.load_cell(c).spec["kernels"]
                   for c in cells)


def test_names_and_shape_of_manifest():
    import re
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in MANIFEST["configs"]] + CELLS
             + [m["name"] for m in MANIFEST["end_to_end"]
                + MANIFEST["per_layer"]])
    assert all(re.match(NAME, n) for n in names)
    assert len(set(names)) == len(names)
    assert "setup_s" in {m["name"] for m in MANIFEST["end_to_end"]}
    assert all(m["bound"] <= 0.25 for m in MANIFEST["end_to_end"])
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_peak_table():
    assert peaks.peak("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")


def _run(cwd, env_extra):
    env = {**os.environ, **env_extra}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "2147483661", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_tpu():
    r = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"metrics"' not in r.stdout


def test_run_refuses_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's paths."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in MANIFEST["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert r.stdout.strip() == ""
