"""MiniCPM-2B's cell on the CPU: a MiniCPM-shaped tiny configuration through
the harness end to end, the files the cell ``minicpm_2b.decode_long`` is
found by, and the reader of ``decode_step_roofline``."""

import json
import time

import numpy as np
import pytest

import chipbench_tiny as tiny
from chipbench import bench, costs

# MiniCPM's block at a tiny width: full multi-head attention with heads of
# 64, tied embeddings, an odd vocabulary, rms_norm_eps 1e-5, 3 layers
CONFIG = dict(tiny.CONFIG, name="minicpm_shaped", hidden_size=128,
              intermediate_size=320, num_attention_heads=2, num_key_value_heads=2,
              num_hidden_layers=3, vocab_size=1001, rms_norm_eps=1e-5,
              tie_word_embeddings=True)
CELL = "minicpm_shaped.decode_long"


@pytest.fixture
def no_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))


def test_minicpm_shaped_cell_is_correct_end_to_end(tmp_path, no_cache):
    data, spec = tiny.make(tmp_path, config=CONFIG, cell=CELL)
    res = bench.run_cell(CELL, 2**33 + 15, 0.5, False, time.perf_counter(),
                         impl="interpret", data=data, spec_path=spec)
    assert res["correct"] is True and res["failed"] == 0
    assert res["checked"]["requests"] >= 1
    for c in res["checks"].values():
        assert 0 <= c["value"] <= c["limit"]


def test_the_cell_and_its_files_are_declared():
    decl = json.loads((tiny.ROOT / "BENCHMARK.json").read_text())
    cell = bench.find_cell(decl, "minicpm_2b.decode_long")
    assert cell["chips"] == 1 and cell["traffic"] == "decode_long"
    (config,) = [c for c in decl["configs"] if c["name"] == cell["config"]]
    cfg = bench.load_json(tiny.ROOT / config["file"])
    assert cfg["reference"] == "dense_decoder"
    assert set(config["reduced"]) == set(cfg["published"])
    for kind, name in (("traffic", cell["traffic"]), ("limits", cell["name"])):
        assert (tiny.ROOT / "chipbench" / kind / f"{name}.json").is_file()
    reported = [m["name"] for m in bench.cell_metrics(decl, cell["name"], True)]
    assert "decode_step_roofline" in reported
    for name in reported:
        assert (tiny.ROOT / "chipbench" / "metrics" / f"{name}.py").is_file()


def _run(cfg, rounds):
    return bench.Run(cfg=cfg, device_kind="TPU v5 lite", setup_s=1.0,
                     window_s=1.0, rounds=rounds)


def _round(plens, steps, decode_s):
    return {"plens": np.asarray(plens),
            "timing": {"decode_s": decode_s, "decode_steps": steps}}


def test_decode_step_roofline_counts_weights_and_valid_kv_per_step():
    reader = bench.load_module("metrics", "decode_step_roofline")
    cfg = bench.load_json(tiny.ROOT / "chipbench" / "configs" / "minicpm_2b.json")
    bw = costs.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    w, kv = costs.matmul_weight_bytes(cfg), costs.kv_bytes_per_token(cfg)
    # two rows of 768 and 1024 tokens, two steps: contexts 769, 1025 then
    # 770, 1026; q read and o written per row and step
    qo = 2 * 36 * 64 * 2 * 40
    least = (2 * w + (769 + 1025 + 770 + 1026) * kv + 4 * qo) / bw
    got = reader.read(_run(cfg, [_round([768, 1024], 2, 0.5)]))
    assert got == pytest.approx(100 * least / 0.5, rel=1e-12)
    # rounds add their least times and their decode seconds
    two = reader.read(_run(cfg, [_round([768, 1024], 2, 0.5)] * 2))
    assert two == pytest.approx(got, rel=1e-12)
    # the cell's bytes a step lie below what 13.8 ms reads at 819 GB/s
    step = reader.read(_run(cfg, [_round([896] * 8, 256, 256 * 0.0138)]))
    assert 0 < step < 100


def test_decode_step_roofline_is_none_without_the_counters():
    reader = bench.load_module("metrics", "decode_step_roofline")
    cfg = bench.load_json(tiny.ROOT / "chipbench" / "configs" / "minicpm_2b.json")
    assert reader.read(_run(cfg, [])) is None
    assert reader.read(_run(cfg, [{"plens": np.asarray([8]), "timing": {}}])) is None
