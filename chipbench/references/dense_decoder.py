"""Plain reference of a dense decoder and of the ProD head.

A Llama-style decoder as the configuration files describe it: token
embedding (times ``scale_emb``), then per layer RMSNorm -> grouped-query
attention with rotary embeddings (rotate-half form, base ``rope_theta``) ->
residual, RMSNorm -> SwiGLU MLP -> residual, each residual branch scaled by
``scale_depth / sqrt(num_hidden_layers)`` where the file gives
``scale_depth``; a final RMSNorm and the output head (the transposed
embedding when ``tie_word_embeddings``), divided by
``hidden_size / dim_model_base`` where the file gives ``dim_model_base``.
One sequence at a time, no cache, no batching, no kernels: float32 with every
matrix product at ``Precision.HIGHEST``, layer by layer in a scan, queries in
blocks so that the scores of a long prompt fit.

The ProD head: ReLU MLP -> softmax over length bins -> the first bin where
the CDF reaches each level q, interpolated linearly inside that bin.

``mode="fp8"`` is the control: the same computation with every matrix
product's inputs rounded to float8 e4m3 first, one scale per row or column
of the contraction, and the head in bfloat16.

This module imports nothing of the program under test.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512


def _dims(cfg):
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return (d, H, cfg["num_key_value_heads"], cfg.get("head_dim") or d // H,
            cfg["num_hidden_layers"])


def _round(x, mode, axes):
    """Round ``x`` to the control's precision, one scale per slice that
    ``axes`` (the contracted axes) runs over."""
    if mode == "reference":
        return x
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    if mode != "fp8":
        raise ValueError(mode)
    s = jnp.maximum(amax, 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq, x, w, mode, x_axes, w_axes):
    x = _round(x.astype(jnp.float32), mode, x_axes)
    w = _round(w.astype(jnp.float32), mode, w_axes)
    return jnp.einsum(eq, x, w, precision=HI)


def _rms_norm(x, gain, eps):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _rope(x, pos, theta):
    """x (S, heads, hd); HF's rotate-half form."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)[None, :]
    emb = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return x * jnp.cos(emb) + rot * jnp.sin(emb)


def _attention(q, k, v, mode):
    """Causal attention of S queries over S keys, q (S, H, hd), k/v (S, KV, hd)."""
    S, H, hd = q.shape
    G = H // k.shape[1]
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    outs = []
    for lo in range(0, S, Q_BLOCK):
        qb = q[lo:lo + Q_BLOCK]
        s = _mm("qhk,shk->hqs", qb, k, mode, (-1,), (-1,)) / math.sqrt(hd)
        qpos = lo + jnp.arange(qb.shape[0])
        mask = jnp.arange(S)[None, :] <= qpos[:, None]
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(_mm("hqs,shk->qhk", p, v, mode, (-1,), (0,)))
    return jnp.concatenate(outs, axis=0)


@functools.partial(jax.jit, static_argnames=("cfg_items", "mode"))
def _forward(W, tokens, read_pos, phi_pos, *, cfg_items, mode):
    cfg = dict(cfg_items)
    d, H, KV, hd, L = _dims(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    res = cfg["scale_depth"] / math.sqrt(L) if "scale_depth" in cfg else 1.0
    S = tokens.shape[0]
    pos = jnp.arange(S)
    x = W["embed"][tokens].astype(jnp.float32) * cfg.get("scale_emb", 1.0)
    stacked = {k: W[k] for k in ("ln1", "ln2", "wq", "wk", "wv", "wo",
                                 "w_gate", "w_up", "w_down")}

    def layer(x, lw):
        h = _rms_norm(x, lw["ln1"], eps)
        q = _rope(_mm("sd,dhk->shk", h, lw["wq"], mode, (-1,), (0,)), pos, theta)
        k = _rope(_mm("sd,dhk->shk", h, lw["wk"], mode, (-1,), (0,)), pos, theta)
        v = _mm("sd,dhk->shk", h, lw["wv"], mode, (-1,), (0,))
        o = _attention(q, k, v, mode)
        x = x + res * _mm("shk,hkd->sd", o, lw["wo"], mode, (-2, -1), (0, 1))
        h = _rms_norm(x, lw["ln2"], eps)
        g = _mm("sd,df->sf", h, lw["w_gate"], mode, (-1,), (0,))
        u = _mm("sd,df->sf", h, lw["w_up"], mode, (-1,), (0,))
        y = _mm("sf,fd->sd", jax.nn.silu(g) * u, lw["w_down"], mode, (-1,), (0,))
        return x + res * y, None

    x, _ = jax.lax.scan(layer, x, stacked)
    hN = _rms_norm(x, W["final_norm"], eps)
    hr = hN[read_pos]
    if "head" in W:
        logits = _mm("nd,dv->nv", hr, W["head"], mode, (-1,), (0,))
    else:
        logits = _mm("nd,vd->nv", hr, W["embed"], mode, (-1,), (-1,))
    if "dim_model_base" in cfg:
        logits = logits / (d / cfg["dim_model_base"])
    return logits, hN[phi_pos]


def forward(W, cfg, tokens, read_pos, phi_pos, mode="reference"):
    """Logits (n, V) float32 at ``read_pos`` of the sequence ``tokens`` (S,),
    and the final-normed hidden state (d,) at ``phi_pos``."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "num_hidden_layers", "rms_norm_eps", "rope_theta",
            "scale_emb", "scale_depth", "dim_model_base")
    items = tuple(sorted((k, cfg[k]) for k in keys if k in cfg))
    return _forward(W, jnp.asarray(tokens, jnp.int32), jnp.asarray(read_pos, jnp.int32),
                    jnp.asarray(phi_pos, jnp.int32), cfg_items=items, mode=mode)


@functools.partial(jax.jit, static_argnames=("qs", "mode"))
def _head(hw, phi, edges, *, qs, mode):
    if mode == "reference":
        dot = lambda a, b: jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                                      precision=HI)
        h = jax.nn.relu(dot(phi, hw["w1"]) + hw["b1"])
        logits = dot(h, hw["w2"]) + hw["b2"]
    else:
        bf = lambda a: a.astype(jnp.bfloat16)
        h = jax.nn.relu(bf(phi) @ bf(hw["w1"]) + bf(hw["b1"]))
        logits = (h @ bf(hw["w2"]) + bf(hw["b2"])).astype(jnp.float32)
    p = jax.nn.softmax(logits, axis=-1)
    cdf = jnp.cumsum(p, axis=-1)
    K = p.shape[-1]
    out = []
    for q in qs:
        hit = cdf >= q
        k = jnp.where(hit.any(-1), jnp.argmax(hit, axis=-1), K - 1)
        pk = jnp.take_along_axis(p, k[:, None], -1)[:, 0]
        before = jnp.take_along_axis(cdf, k[:, None], -1)[:, 0] - pk
        t = jnp.clip((q - before) / jnp.maximum(pk, 1e-12), 0.0, 1.0)
        out.append(edges[k] + t * (edges[k + 1] - edges[k]))
    return p, jnp.stack(out, axis=-1)


def head_quantiles(hw, phi, edges, qs, mode="reference"):
    """The ProD head's length distribution for hidden states ``phi`` (n, d):
    bin probabilities (n, K) and quantiles (n, len(qs)) in tokens."""
    return _head(hw, jnp.asarray(phi, jnp.float32), jnp.asarray(edges, jnp.float32),
                 qs=tuple(float(q) for q in qs), mode=mode)
