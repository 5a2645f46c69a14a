"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth),
and for the served decode attention, ``models.attention.decode_attention``.

Tests sweep shapes/dtypes and ``assert_allclose`` kernel-vs-oracle; the model
code paths also use these (via ``ops``' ``xla`` impl) on non-TPU backends.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def flash_attention_ref(
    q: jax.Array,            # (B, Sq, H, hd)
    k: jax.Array,            # (B, Skv, KV, hd)
    v: jax.Array,            # (B, Skv, KV, hd)
    lengths: Optional[jax.Array] = None,   # (B,) real tokens per row
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
) -> jax.Array:
    """With ``lengths``, keys at or past a row's length are masked and its
    queries there give zeros, as the kernel does."""
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    qpos = q_offset + jnp.arange(Sq)
    kpos = jnp.arange(Skv)
    mask = jnp.ones((1, Sq, Skv), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window:
        mask &= (qpos[:, None] - kpos[None, :]) < window
    if lengths is not None:
        mask &= kpos[None, None, :] < lengths[:, None, None]
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bkgqh", p, v.astype(jnp.float32))
    o = jnp.moveaxis(o, 3, 1).reshape(B, Sq, H, hd)
    if lengths is not None:
        o = jnp.where((qpos[None, :] < lengths[:, None])[..., None, None], o, 0)
    return o.astype(q.dtype)


def decode_attention_ref(
    q: jax.Array,            # (B, H, hd)
    k: jax.Array,            # (B, Sc, KV, hd)
    v: jax.Array,            # (B, Sc, KV, hd)
    lengths: jax.Array,      # (B,) int32 — valid cache prefix
) -> jax.Array:
    B, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    valid = jnp.arange(k.shape[1])[None, :] < lengths[:, None]
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskh->bkgh", p, v.astype(jnp.float32))
    return o.reshape(B, H, hd).astype(q.dtype)


def prod_head_ref(
    phi: jax.Array,       # (B, d) — served LLM last hidden state
    w1: jax.Array,        # (d, hidden)
    b1: jax.Array,        # (hidden,)
    w2: jax.Array,        # (hidden, K)
    b2: jax.Array,        # (K,)
    edges: jax.Array,     # (K+1,) bin edges
    qs: Optional[jax.Array] = None,   # (Q,) CDF levels; None -> median only
) -> Tuple[jax.Array, jax.Array]:
    """ProD predictor head (paper §2.4): 2-layer MLP -> softmax over K bins
    -> CDF-crossing quantile decode with in-bin linear interpolation.

    Returns (probs (B, K) fp32, median_estimate (B,) fp32) when ``qs`` is
    None, else (probs, quants (B, Q) fp32) — one column per CDF level.
    """
    with jax.named_scope("fusedkernel_prod_head"):
        return _prod_head_body(phi, w1, b1, w2, b2, edges, qs)


def _prod_head_body(phi, w1, b1, w2, b2, edges, qs=None):
    single = qs is None
    qs = jnp.array([0.5], jnp.float32) if single else jnp.asarray(qs, jnp.float32)
    h = jax.nn.relu(phi.astype(jnp.float32) @ w1.astype(jnp.float32) + b1.astype(jnp.float32))
    logits = h @ w2.astype(jnp.float32) + b2.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    cdf = jnp.cumsum(probs, axis=-1)
    K = probs.shape[-1]
    crossed = cdf[:, None, :] >= qs[None, :, None]                # (B, Q, K)
    # first crossing, clamped to the last bin when float32 rounding keeps the
    # CDF below q (q→1) — same rule as the Pallas kernel, so impls agree
    k_star = jnp.min(jnp.where(crossed, jnp.arange(K)[None, None, :], K - 1),
                     axis=-1)
    cdf_prev = jnp.where(
        k_star > 0,
        jnp.take_along_axis(cdf[:, None, :].repeat(qs.shape[0], 1),
                            jnp.maximum(k_star - 1, 0)[..., None],
                            axis=-1)[..., 0], 0.0)
    p_k = jnp.take_along_axis(probs[:, None, :].repeat(qs.shape[0], 1),
                              k_star[..., None], axis=-1)[..., 0]
    t = jnp.clip((qs[None, :] - cdf_prev) / jnp.maximum(p_k, 1e-12), 0.0, 1.0)
    left = edges[k_star]
    right = edges[k_star + 1]
    quants = left + t * (right - left)
    if single:
        return probs, quants[:, 0]
    return probs, quants
