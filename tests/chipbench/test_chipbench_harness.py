"""The harness end to end at a tiny size on the CPU, with the head in
interpret mode, through ``bench.run_cell`` (the command itself refuses to run
without a TPU), and the files it finds by name."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

import chipbench_tiny as tiny
from chipbench import bench

E2E = ("tokens_per_s", "latency_p95_ms", "setup_s")


@pytest.fixture
def no_cache(tmp_path, monkeypatch):
    # JAX reads the variable only at start-up, so setting it keeps the run
    # from switching this process's persistent cache on
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))


def run(data, spec, workload=tiny.CELL, seed=2**33 + 7, **kw):
    return bench.run_cell(workload, seed, 0.5, False, time.perf_counter(),
                          impl="interpret", data=data, spec_path=spec, **kw)


def test_tiny_cell_end_to_end_matches_the_contract(tmp_path, no_cache):
    data, spec = tiny.make(tmp_path)
    res = run(data, spec)
    line = json.loads(json.dumps(res))
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= tiny.MIX["clients"]
    assert set(line["metrics"]) == set(E2E)
    for m in line["metrics"].values():
        assert m["value"] > 0 and set(m) == {"value", "unit"}
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    assert line["device"]["platform"] == "cpu"
    assert line["window_compiles"] == 0
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"} and 0 <= c["value"] <= c["limit"]


def test_added_config_traffic_and_metric_are_found_by_name(tmp_path, no_cache):
    """A cell added as files only — a configuration, a traffic mix, limits
    and a per-layer metric that no file names yet — runs without an edit."""
    cfg = dict(tiny.CONFIG, name="other_model", num_hidden_layers=1)
    mix = dict(tiny.MIX, clients=2, max_new=3)
    data, spec = tiny.make(tmp_path, config=cfg, mix=mix, cell="other_model.burst")
    (data / "metrics" / "rounds_served.py").write_text(
        "def read(run):\n    return len(run.rounds)\n")
    (data / "metrics" / "never_read.py").write_text(
        "def read(run):\n    return None\n")
    decl = json.loads(spec.read_text())
    decl["per_layer"] = [
        {"name": n, "unit": "1", "better": "higher", "source": "program_counter",
         "layer": "engine", "moves": "tokens_per_s"} for n in ("rounds_served", "never_read")]
    spec.write_text(json.dumps(decl))
    t = bench.run_cell("other_model.burst", 5, 0.3, False, time.perf_counter(),
                       impl="interpret", data=data, spec_path=spec)
    assert t["correct"] and set(t["metrics"]) == set(E2E)
    metrics = [m["name"] for m in bench.cell_metrics(decl, "other_model.burst", True)]
    assert metrics == ["rounds_served", "never_read"]
    run_ = bench.Run(cfg=cfg, device_kind="cpu", setup_s=1.0, window_s=1.0,
                     rounds=[{}, {}])
    assert bench.load_module("metrics", "rounds_served", data).read(run_) == 2
    assert bench.load_module("metrics", "never_read", data).read(run_) is None


def test_missing_file_names_what_is_missing(tmp_path):
    with pytest.raises(FileNotFoundError, match="no metric 'absent'"):
        bench.load_module("metrics", "absent", tmp_path)


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "qwen25_7b.decode",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_without_a_tpu_exits_nonzero_and_prints_no_result():
    p = _command(tiny.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr


def test_command_in_a_directory_of_only_the_benchmark_exits_nonzero(tmp_path):
    decl = json.loads((tiny.ROOT / "BENCHMARK.json").read_text())
    shutil.copy(tiny.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in decl["paths"]:
        shutil.copytree(tiny.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("change", [{"scale_emb": 12}, {"scale_depth": 1.4},
                                    {"dim_model_base": 64}, {"attention_bias": True}],
                         ids=["scale_emb", "scale_depth", "dim_model_base", "attention_bias"])
def test_entry_refuses_arithmetic_the_program_lacks(change):
    """A file that asks for what the program cannot compute is refused, not
    served as another model beside a reference that computes it."""
    entry = bench.load_module("entries", "static_generate")
    entry.model_config(dict(tiny.CONFIG, name="tiny"))
    with pytest.raises(ValueError, match="the program has no"):
        entry.model_config(dict(tiny.CONFIG, name="tiny", **change))
