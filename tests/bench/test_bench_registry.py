"""Everything BENCHMARK.json names loads by name; a run off a TPU fails."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import pytest  # noqa: E402

from bench import harness, peaks, weights  # noqa: E402

BENCH = harness.load_benchmark()


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads(name):
    c = harness.cell(BENCH, name)
    assert c["config"]["name"] == c["workload"]["config"]
    harness.kind(c["traffic"]["kind"])
    for section in ("end_to_end", "per_layer"):
        assert harness.cell_metrics(BENCH, name, section)
    assert "setup_s" in [m["name"] for m in
                         harness.cell_metrics(BENCH, name, "end_to_end")]


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_every_config_states_its_cuts(conf):
    c = json.loads((ROOT / conf["file"]).read_text())
    assert c["source"] == conf["source"]
    assert sorted(c["reduced"]) == sorted(conf["reduced"])
    assert c["assumed"] and c["deployment"]
    assert weights.abstract(c)


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_every_metric_reader_loads(metric):
    assert callable(harness.metric_reader(metric["name"]))
    for w in metric.get("workloads", []):
        harness.cell(BENCH, w)


def test_a_dropped_in_metric_is_found(tmp_path, monkeypatch):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "probe.layer.py").write_text(
        "def read(ctx):\n    return ctx.value * 2\n")
    monkeypatch.setattr(harness, "BENCH_DIR", tmp_path)

    class Ctx:
        value = 21
    assert harness.metric_reader("probe.layer")(Ctx) == 42


def test_unknown_device_kind_has_no_peak():
    assert peaks.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("TPU v99")


def _run(cwd, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "train.l12.shexp",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_fails_without_a_result(tmp_path):
    out = _run(ROOT, tmp_path)
    assert out.returncode == 2, out.stderr[-2000:]
    assert out.stdout == ""
    assert "no TPU" in out.stderr


def test_bare_benchmark_directory_fails(tmp_path):
    bare = tmp_path / "bare"
    bare.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", bare)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, bare / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(bare, tmp_path)
    assert out.returncode != 0
    assert out.stdout == ""
