"""Attention: GQA/MQA projections, blocked (flash-style) XLA attention for
training and the encoder-decoder, and KV-cached decode attention (full cache
+ sliding ring). On TPU the served prefill runs the Pallas kernel in
``repro.kernels.flash_attention`` instead (``transformer._apply_attn_seq``).

The blocked implementation never materializes an (S, S) score matrix — it
scans KV blocks with an online softmax, and has a backward pass.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig
from repro.common.scopes import scoped_kernel_vjp as _scoped_kernel_vjp
from repro.models.layers import ParamSpec, rms_norm
from repro.models.rope import apply_rope

NEG_INF = -1e30


def attn_spec(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    hd = cfg.head_dim
    # explicit 1/sqrt(fan_in): the default rule reads shape[-2], which for
    # these 3-D projections is a head count, not the contracted width (at
    # d=3584 it made random-weight attention logits ~300 wide, i.e. argmax)
    s_in = 1.0 / math.sqrt(cfg.d_model)
    s_out = 1.0 / math.sqrt(cfg.n_heads * hd)
    s = {
        "wq": ParamSpec((cfg.d_model, cfg.n_heads, hd), ("embed", "heads", "head_dim"),
                        scale=s_in),
        "wk": ParamSpec((cfg.d_model, cfg.n_kv_heads, hd), ("embed", "kv_heads", "head_dim"),
                        scale=s_in),
        "wv": ParamSpec((cfg.d_model, cfg.n_kv_heads, hd), ("embed", "kv_heads", "head_dim"),
                        scale=s_in),
        "wo": ParamSpec((cfg.n_heads, hd, cfg.d_model), ("heads", "head_dim", "embed"),
                        scale=s_out),
    }
    if cfg.qk_norm:
        s["q_norm"] = ParamSpec((hd,), ("head_dim",), init="zeros")
        s["k_norm"] = ParamSpec((hd,), ("head_dim",), init="zeros")
    return s


def qkv_project(
    p: Dict[str, jax.Array],
    x: jax.Array,                   # (B, S, d)
    cfg: ModelConfig,
    angles: Optional[jax.Array],    # (B, S, hd//2) or None (no rope: whisper)
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if angles is not None:
        q = apply_rope(q, angles)
        k = apply_rope(k, angles)
    return q, k, v


def out_project(p: Dict[str, jax.Array], o: jax.Array) -> jax.Array:
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def _pad_to(x: jax.Array, axis: int, mult: int):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


def blocked_attention(
    q: jax.Array,                 # (B, Sq, H, hd)
    k: jax.Array,                 # (B, Skv, KV, hd)
    v: jax.Array,                 # (B, Skv, KV, hd)
    *,
    causal: bool = True,
    q_offset: int = 0,            # absolute position of q[0] (cross/enc: ignored)
    window: int = 0,              # 0 = unwindowed
    kv_valid: Optional[jax.Array] = None,  # (B, Skv) bool
    block_q: int = 512,
    block_kv: int = 512,
    causal_skip: bool = False,    # skip fully-masked KV blocks (perf variant)
) -> jax.Array:
    """Online-softmax blocked attention. Returns (B, Sq, H, hd)."""
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)

    q, Sq0 = _pad_to(q, 1, block_q)
    k, Skv0 = _pad_to(k, 1, block_kv)
    v, _ = _pad_to(v, 1, block_kv)
    Sqp, Skvp = q.shape[1], k.shape[1]
    nq, nkv = Sqp // block_q, Skvp // block_kv

    q = q.reshape(B, nq, block_q, KV, G, hd)
    kb = jnp.moveaxis(k.reshape(B, nkv, block_kv, KV, hd), 1, 0)   # (nkv, B, bkv, KV, hd)
    vb = jnp.moveaxis(v.reshape(B, nkv, block_kv, KV, hd), 1, 0)
    if kv_valid is not None:
        kv_valid_p, _ = _pad_to(kv_valid, 1, block_kv)
        kvb = jnp.moveaxis(kv_valid_p.reshape(B, nkv, block_kv), 1, 0)  # (nkv, B, bkv)
    else:
        kvb = None

    def q_chunk_attend(qc, qpos, n_blocks, kb, vb, kvb):
        # qc: (B, bq, KV, G, hd); qpos: (bq,)
        def body(carry, blk):
            m, l, acc = carry
            if kvb is None:
                kblk, vblk, kp = blk
                valid = None
            else:
                kblk, vblk, kp, valid = blk
            s = jnp.einsum(
                "bqkgh,bskh->bkgqs", qc, kblk, preferred_element_type=jnp.float32
            ) * scale  # (B, KV, G, bq, bkv) fp32
            mask = jnp.ones((block_q, block_kv), bool)
            if causal:
                mask &= kp[None, :] <= qpos[:, None]
            if window:
                mask &= (qpos[:, None] - kp[None, :]) < window
            mask &= (kp < Skv0)[None, :]
            m_full = mask[None, None, None]
            if valid is not None:
                m_full = m_full & valid[:, None, None, None, :]
            s = jnp.where(m_full, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bskh->bkgqh", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32,
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, block_q), jnp.float32)
        a0 = jnp.zeros((B, KV, G, block_q, hd), jnp.float32)
        # rebuilt here (not closed over) so the custom_vjp bwd re-trace is pure
        kpos = jnp.arange(Skvp, dtype=jnp.int32).reshape(nkv, block_kv)
        xs = (kb[:n_blocks], vb[:n_blocks], kpos[:n_blocks])
        if kvb is not None:
            xs = xs + (kvb[:n_blocks],)
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), xs)
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out  # (B, KV, G, bq, hd)

    def attend_all(q_, kb_, vb_, kvb_):
        outs = []
        for i in range(nq):
            qpos = q_offset + i * block_q + jnp.arange(block_q, dtype=jnp.int32)
            if causal and causal_skip and kv_valid is None and window == 0:
                # only KV blocks whose first position is visible to this chunk
                last_q = q_offset + (i + 1) * block_q - 1
                n_blocks = min(nkv, max(1, -(-min(last_q + 1, Skv0) // block_kv)))
            else:
                n_blocks = nkv
            outs.append(q_chunk_attend(q_[:, i], qpos, n_blocks, kb_, vb_, kvb_))
        return jnp.stack(outs, axis=1)  # (B, nq, KV, G, bq, hd)

    # custom_vjp so BOTH passes carry the fusedkernel scope: this region is
    # XLA's, forward and a recompute-based backward, and the roofline analyzer
    # treats its scoped intermediates as VMEM-resident. On TPU the served
    # prefill does not run it: its attention is the Pallas kernel, under the
    # same scope.
    core = _scoped_kernel_vjp("fusedkernel_flash_attention", attend_all)
    out = core(q, kb, vb, kvb)
    out = jnp.moveaxis(out, -2, 2).reshape(B, Sqp, KV * G, hd)
    return out[:, :Sq0].astype(v.dtype)


def kv_lanes(t: jax.Array) -> jax.Array:
    """(..., KV, hd) -> (..., KV·hd): decode caches keep heads and head width
    merged in their minor dimension, so that narrow heads (hd 64) stay
    lane-dense on the TPU instead of being laid out with the sequence minor."""
    return t.reshape(*t.shape[:-2], t.shape[-2] * t.shape[-1])


def decode_attention(
    q: jax.Array,                # (B, 1, H, hd)
    k_cache: jax.Array,          # (B, Sc, KV·hd): heads and head width merged
    v_cache: jax.Array,          # (B, Sc, KV·hd)
    valid: jax.Array,            # (B, Sc) bool — which cache slots participate
) -> jax.Array:
    """Single-token attention against a lane-dense KV cache. Returns (B, 1, H, hd).

    Both products contract the cache's merged minor dimension whole, as
    matrix products: the scores against a block-diagonal query (each query
    head nonzero only on its K/V head's lanes), the values for every head
    before each head keeps its own K/V head's lanes. The zeros add exactly
    nothing; the cache is read once, in its own layout."""
    B, _, H, hd = q.shape
    C = k_cache.shape[-1]
    KV = C // hd
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    with jax.named_scope("fusedkernel_decode_attention"):
        own = (jnp.arange(C)[:, None] // hd) == (jnp.arange(H)[None, :] // G)  # (C, H)
        q_lanes = jnp.tile(q.reshape(B, H, hd), (1, 1, KV))                   # (B, H, C)
        q_bd = jnp.where(own.T[None], q_lanes, 0).astype(k_cache.dtype)
        s = jnp.einsum(
            "bhc,bsc->bhs", q_bd, k_cache, preferred_element_type=jnp.float32
        ) * scale
        s = jnp.where(valid[:, None, :], s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o_all = jnp.einsum(
            "bhs,bsc->bhc", (p / jnp.maximum(l, 1e-30)).astype(v_cache.dtype),
            v_cache, preferred_element_type=jnp.float32,
        )                                                                      # (B, H, C)
        o = jnp.sum(jnp.where(own.T[None], o_all, 0).reshape(B, H, KV, hd), axis=2)
    return o.reshape(B, 1, H, hd).astype(v_cache.dtype)


# ---------------------------------------------------------------------------
# KV caches (static-shape, TPU-idiomatic)
# ---------------------------------------------------------------------------


def full_cache_valid(lengths: jax.Array, S: int) -> jax.Array:
    """(B,) current lengths (token count incl. the one just written) -> (B, S)."""
    return jnp.arange(S, dtype=jnp.int32)[None, :] < lengths[:, None]


def ring_cache_valid(lengths: jax.Array, window: int) -> jax.Array:
    return jnp.arange(window, dtype=jnp.int32)[None, :] < jnp.minimum(
        lengths[:, None], window
    )
