"""Operations and bytes of a dense decoder's work, from its shapes alone.

Counts come from the configuration file, never from a compiled program, so
that a change to the implementation cannot change the yardstick. A
multiply-add is two operations; weights and cache are bfloat16 (2 bytes).

* ``prefill_flops(cfg, P)`` — one request's prefill over its P real tokens:
  every matrix product of the body for each token, causal attention over the
  real tokens, and the output head for the one token that is unembedded.
* ``decode_flops(cfg, c)`` — one token decoded at context length c (keys
  attended, the new one included).
* ``decode_step_bytes(cfg, B, cache_len)`` — what one batched decode step
  reads from memory as the program lays it out: all matrix weights and the
  whole allocated K/V cache.
* ``decode_attention_cost`` / ``prefill_attention_cost`` — what the
  attention kernels need: only the valid K/V, only the real tokens.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

BYTES = 2  # bfloat16
PEAKS = Path(__file__).resolve().parent / "peaks.json"


def _dims(cfg):
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return (d, H, cfg["num_key_value_heads"], cfg.get("head_dim") or d // H,
            cfg["intermediate_size"], cfg["vocab_size"], cfg["num_hidden_layers"])


def layer_matmul_params(cfg) -> int:
    d, H, KV, hd, F, _, _ = _dims(cfg)
    return 2 * d * H * hd + 2 * d * KV * hd + 3 * d * F


def head_params(cfg) -> int:
    d, *_, V, _ = _dims(cfg)
    return d * V


def param_count(cfg) -> int:
    """Every parameter: embedding, the output head unless tied, the layers'
    matrices and norm gains, and the final norm."""
    d, *_, V, L = _dims(cfg)
    n = V * d + L * (layer_matmul_params(cfg) + 2 * d) + d
    return n if cfg["tie_word_embeddings"] else n + d * V


def matmul_weight_bytes(cfg) -> int:
    """Bytes of every weight a decode step reads: the layers' matrices and
    the output head (the embedding table is gathered, a row per token)."""
    _, *_, L = _dims(cfg)
    return BYTES * (L * layer_matmul_params(cfg) + head_params(cfg))


def kv_bytes_per_token(cfg) -> int:
    _, _, KV, hd, _, _, L = _dims(cfg)
    return 2 * KV * hd * BYTES * L


def attention_flops(cfg, n_keys_total) -> float:
    """Score and value products over ``n_keys_total`` (query, key) pairs."""
    _, H, _, hd, _, _, L = _dims(cfg)
    return 4.0 * H * hd * L * n_keys_total


def prefill_flops(cfg, P) -> float:
    _, *_, L = _dims(cfg)
    P = np.asarray(P, np.float64)
    return float(np.sum(2.0 * L * layer_matmul_params(cfg) * P
                        + attention_flops(cfg, P * (P + 1) / 2)
                        + 2.0 * head_params(cfg)))


def decode_flops(cfg, c) -> float:
    _, *_, L = _dims(cfg)
    c = np.asarray(c, np.float64)
    return float(np.sum(2.0 * L * layer_matmul_params(cfg) + attention_flops(cfg, c)
                        + 2.0 * head_params(cfg)))


def decode_step_bytes(cfg, B: int, cache_len: int) -> int:
    return matmul_weight_bytes(cfg) + B * cache_len * kv_bytes_per_token(cfg)


def request_flops(cfg, P: int, n_out: int) -> float:
    """Work delivered for one request: its prefill (which yields the first
    token) and the n_out - 1 decode steps that yield the rest, at contexts
    P + 1 .. P + n_out - 1."""
    return prefill_flops(cfg, P) + (decode_flops(cfg, P + np.arange(1, n_out))
                                    if n_out > 1 else 0.0)


def decode_attention_cost(cfg, valid) -> tuple:
    """(flops, bytes) of single-token attention over ``valid`` keys per row
    (any shape): the valid K/V read once, q read and o written."""
    _, H, _, hd, _, _, L = _dims(cfg)
    valid = np.asarray(valid, np.float64)
    n = valid.size
    flops = attention_flops(cfg, valid.sum())
    nbytes = valid.sum() * kv_bytes_per_token(cfg) + n * 2 * H * hd * BYTES * L
    return flops, nbytes


def prefill_attention_cost(cfg, P) -> tuple:
    """(flops, bytes) of causal attention over each request's P real tokens:
    q, k, v read and o written once."""
    _, H, KV, hd, _, _, L = _dims(cfg)
    P = np.asarray(P, np.float64)
    flops = attention_flops(cfg, np.sum(P * (P + 1) / 2))
    nbytes = np.sum(P) * (2 * H * hd + 2 * KV * hd) * BYTES * L
    return flops, nbytes


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a kind missing from the table is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS.name}")
    return table[device_kind]


def roofline_share(flops, nbytes, seconds, peak) -> float:
    """Least time the chip could take (the larger of operations over peak
    FLOP/s and bytes over peak bytes/s) over the time taken, in %."""
    least = max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
