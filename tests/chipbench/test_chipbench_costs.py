"""Operations, bytes and peaks of the benchmark's yardstick, from shapes."""

import json

import numpy as np
import pytest

from chipbench_tiny import ROOT
from chipbench import costs

CONFIGS = ROOT / "chipbench" / "configs"


def config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_qwen_param_count_at_14_layers():
    assert costs.param_count(config("qwen25_7b")) == 4_352_742_912


def test_minicpm_param_count_from_its_file():
    c = config("minicpm_2b")
    d, F, V, L = (c[k] for k in ("hidden_size", "intermediate_size", "vocab_size",
                                 "num_hidden_layers"))
    hd = d // c["num_attention_heads"]
    per_layer = (d * c["num_attention_heads"] * hd * 2          # q, o
                 + d * c["num_key_value_heads"] * hd * 2         # k, v
                 + 3 * d * F + 2 * d)                            # MLP, two norms
    assert c["tie_word_embeddings"]
    assert costs.param_count(c) == V * d + L * per_layer + d == 2_724_880_896


def test_cell1_decode_step_bytes():
    # 14 layers' matrices + the untied head (7.61 GB) and the whole allocated
    # cache: 32 rows x (512 + 256) slots x 28,672 B per token
    q = config("qwen25_7b")
    assert costs.kv_bytes_per_token(q) == 28_672
    assert costs.matmul_weight_bytes(q) == 7_615_283_200
    assert costs.decode_step_bytes(q, 32, 768) == 7_615_283_200 + 32 * 768 * 28_672


def test_minicpm_kv_is_12_9_times_qwens():
    ratio = costs.kv_bytes_per_token(config("minicpm_2b")) / costs.kv_bytes_per_token(
        config("qwen25_7b"))
    assert ratio == pytest.approx(12.857, abs=1e-3)


def test_request_flops_is_prefill_plus_its_decode_steps():
    q = config("qwen25_7b")
    one = costs.request_flops(q, 100, 1)
    assert one == costs.prefill_flops(q, 100)
    three = costs.request_flops(q, 100, 3)
    assert three == pytest.approx(one + costs.decode_flops(q, 101) + costs.decode_flops(q, 102))


def test_attention_costs_count_only_real_tokens():
    q = config("qwen25_7b")
    f, b = costs.decode_attention_cost(q, np.array([[10, 20]]))
    assert b == 30 * 28_672 + 2 * (2 * 28 * 128 * 2 * 14)
    assert f == 4.0 * 28 * 128 * 14 * 30
    f, b = costs.prefill_attention_cost(q, [4])
    assert f == 4.0 * 28 * 128 * 14 * 10          # 4 + 3 + 2 + 1 pairs


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        costs.peaks("TPU v9 imaginary")


def test_v5e_peaks_and_roofline_share():
    p = costs.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    # bound by bytes: 819 MB take 1 ms at peak; done in 2 ms is 50%
    assert costs.roofline_share(1.0, 819e6, 2e-3, p) == pytest.approx(50.0)
