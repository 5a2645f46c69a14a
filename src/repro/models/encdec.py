"""Whisper-style encoder-decoder backbone [arXiv:2212.04356].

The audio frontend (mel-spectrogram + conv downsampling) is a stub per the
assignment: the encoder consumes precomputed frame embeddings
(B, encoder_seq, d_model). LayerNorm + plain-GELU MLPs, sinusoidal positions
(computed on the fly so arbitrarily long decode positions work), no RoPE.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig
from repro.models import attention as attn
from repro.models.layers import (
    ParamSpec,
    embed_spec,
    layer_norm,
    mlp_apply,
    mlp_spec,
    stack_specs,
    unembed,
)


def _ln_spec(d: int) -> Dict[str, ParamSpec]:
    return {"scale": ParamSpec((d,), ("embed",), init="ones"),
            "bias": ParamSpec((d,), ("embed",), init="zeros")}


def _ln(p, x, eps):
    return layer_norm(x, p["scale"], p["bias"], eps)


def _enc_layer_spec(cfg: ModelConfig):
    return {"ln1": _ln_spec(cfg.d_model), "attn": attn.attn_spec(cfg),
            "ln2": _ln_spec(cfg.d_model),
            "mlp": mlp_spec(cfg.d_model, cfg.d_ff, "gelu")}


def _dec_layer_spec(cfg: ModelConfig):
    return {"ln1": _ln_spec(cfg.d_model), "self_attn": attn.attn_spec(cfg),
            "ln2": _ln_spec(cfg.d_model), "cross_attn": attn.attn_spec(cfg),
            "ln3": _ln_spec(cfg.d_model),
            "mlp": mlp_spec(cfg.d_model, cfg.d_ff, "gelu")}


def whisper_spec(cfg: ModelConfig) -> Dict[str, Any]:
    return {
        "embed": embed_spec(cfg.vocab_size, cfg.d_model),
        "encoder": stack_specs(_enc_layer_spec(cfg), cfg.n_encoder_layers),
        "enc_final_ln": _ln_spec(cfg.d_model),
        "decoder": stack_specs(_dec_layer_spec(cfg), cfg.n_layers),
        "dec_final_ln": _ln_spec(cfg.d_model),
    }


def sinusoid_at(positions: jax.Array, d_model: int) -> jax.Array:
    """Sinusoidal embedding rows for arbitrary integer positions (..., d)."""
    half = d_model // 2
    freqs = jnp.exp(
        -jnp.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / max(half - 1, 1)
    )
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def encode(params, cfg: ModelConfig, enc_embeds: jax.Array,
           block_q: int = 512, block_kv: int = 512) -> jax.Array:
    """Bidirectional encoder over stub frame embeddings (B, Senc, d)."""
    B, S, d = enc_embeds.shape
    x = enc_embeds + sinusoid_at(jnp.arange(S), d)[None].astype(enc_embeds.dtype)

    def layer(x, lp):
        h = _ln(lp["ln1"], x, cfg.norm_eps)
        q, k, v = attn.qkv_project(lp["attn"], h, cfg, None)
        o = attn.blocked_attention(q, k, v, causal=False,
                                   block_q=block_q, block_kv=block_kv)
        x = x + attn.out_project(lp["attn"], o)
        h = _ln(lp["ln2"], x, cfg.norm_eps)
        return x + mlp_apply(lp["mlp"], h, "gelu"), None

    x, _ = jax.lax.scan(layer, x, params["encoder"])
    return _ln(params["enc_final_ln"], x, cfg.norm_eps)


def decoder_forward(
    params, cfg: ModelConfig, tokens: jax.Array, enc_out: jax.Array,
    mode: str = "train", block_q: int = 512, block_kv: int = 512,
    attn_valid: Optional[jax.Array] = None, logits_mode: str = "all",
):
    """Causal decoder with cross-attention. Returns (logits, hidden, cache)."""
    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    x = x + sinusoid_at(jnp.arange(S), cfg.d_model)[None].astype(x.dtype)

    def layer(x, lp):
        h = _ln(lp["ln1"], x, cfg.norm_eps)
        q, k, v = attn.qkv_project(lp["self_attn"], h, cfg, None)
        o = attn.blocked_attention(q, k, v, causal=True, kv_valid=attn_valid,
                                   block_q=block_q, block_kv=block_kv)
        x = x + attn.out_project(lp["self_attn"], o)
        h = _ln(lp["ln2"], x, cfg.norm_eps)
        qc, kc, vc = _cross_qkv(lp["cross_attn"], h, enc_out, cfg)
        oc = attn.blocked_attention(qc, kc, vc, causal=False,
                                    block_q=block_q, block_kv=block_kv)
        x = x + attn.out_project(lp["cross_attn"], oc)
        h = _ln(lp["ln3"], x, cfg.norm_eps)
        x = x + mlp_apply(lp["mlp"], h, "gelu")
        cache = {"k": k, "v": v, "ck": kc, "cv": vc} if mode == "prefill" else {}
        return x, cache

    x, cache = jax.lax.scan(layer, x, params["decoder"])
    hidden = _ln(params["dec_final_ln"], x, cfg.norm_eps)
    logits = unembed(hidden, params["embed"], None) if logits_mode == "all" else None
    return logits, hidden, (cache if mode == "prefill" else None)


def _cross_qkv(p, h_dec, enc_out, cfg):
    q = jnp.einsum("bsd,dhk->bshk", h_dec, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"])
    return q, k, v


def whisper_loss(params, cfg: ModelConfig, tokens, enc_embeds, loss_mask=None):
    enc_out = encode(params, cfg, enc_embeds)
    logits, _, _ = decoder_forward(params, cfg, tokens, enc_out)
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if loss_mask is not None:
        m = loss_mask[:, 1:].astype(jnp.float32)
        loss = jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
    else:
        loss = jnp.mean(nll)
    return loss, {"loss": loss}


def decoder_cache_spec(cfg: ModelConfig, batch: int, cache_len: int):
    """Self-attn KV (ring if cfg.attn_window set) + static cross KV."""
    W = cfg.attn_window
    Sc = min(W, cache_len) if W else cache_len
    L = cfg.n_layers
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    ax = ("layers", "batch", "cache_seq", "cache_kv_heads", "head_dim")
    return {
        "k": ParamSpec((L, batch, Sc, kv, hd), ax),
        "v": ParamSpec((L, batch, Sc, kv, hd), ax),
        "ck": ParamSpec((L, batch, cfg.encoder_seq, kv, hd), ax),
        "cv": ParamSpec((L, batch, cfg.encoder_seq, kv, hd), ax),
    }


def decoder_decode_step(
    params, cfg: ModelConfig, tokens: jax.Array, cache: Dict[str, jax.Array],
    pos: jax.Array, lengths: jax.Array,
):
    """One decoder token with cached self-KV + precomputed cross-KV.

    cache: {"k","v": (L,B,Sc,kv,hd), "ck","cv": (L,B,Senc,kv,hd)}
    """
    B = tokens.shape[0]
    x = jnp.take(params["embed"], tokens[:, None], axis=0).astype(cfg.dtype)
    x = x + sinusoid_at(pos[:, None], cfg.d_model).astype(x.dtype)
    W = cfg.attn_window
    Sc = cache["k"].shape[2]
    ring = bool(W) and Sc == W
    bidx = jnp.arange(B)

    def layer(x, xs):
        lp, kc, vc, ck, cv = xs
        h = _ln(lp["ln1"], x, cfg.norm_eps)
        q, k, v = attn.qkv_project(lp["self_attn"], h, cfg, None)
        if ring:
            slot = jnp.mod(pos, Sc)
            kc = kc.at[bidx, slot].set(k[:, 0].astype(kc.dtype))
            vc = vc.at[bidx, slot].set(v[:, 0].astype(vc.dtype))
            valid = attn.ring_cache_valid(lengths, Sc)
        else:
            kc = kc.at[bidx, pos].set(k[:, 0].astype(kc.dtype))
            vc = vc.at[bidx, pos].set(v[:, 0].astype(vc.dtype))
            valid = attn.full_cache_valid(lengths, Sc)
        o = attn.decode_attention(q, attn.kv_lanes(kc), attn.kv_lanes(vc), valid)
        x = x + attn.out_project(lp["self_attn"], o)
        h = _ln(lp["ln2"], x, cfg.norm_eps)
        qc = jnp.einsum("bsd,dhk->bshk", h, lp["cross_attn"]["wq"])
        all_valid = jnp.ones((B, ck.shape[1]), bool)
        oc = attn.decode_attention(qc, attn.kv_lanes(ck), attn.kv_lanes(cv), all_valid)
        x = x + attn.out_project(lp["cross_attn"], oc)
        h = _ln(lp["ln3"], x, cfg.norm_eps)
        x = x + mlp_apply(lp["mlp"], h, "gelu")
        return x, (kc, vc)

    x, (new_k, new_v) = jax.lax.scan(
        layer, x, (params["decoder"], cache["k"], cache["v"], cache["ck"], cache["cv"])
    )
    hidden = _ln(params["dec_final_ln"], x[:, 0], cfg.norm_eps)
    logits = unembed(hidden, params["embed"], None)
    new_cache = {"k": new_k, "v": new_v, "ck": cache["ck"], "cv": cache["cv"]}
    return logits, hidden, new_cache
