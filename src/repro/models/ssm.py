"""Mamba2 / SSD (state-space duality) blocks [arXiv:2405.21060].

Training/prefill uses the chunked SSD form: a `lax.scan` over sequence chunks
carrying the (B, H, P, N) inter-chunk state; within a chunk the computation is
attention-like matmuls (MXU-friendly). Decode is the O(1) recurrence.

Layout: d_inner = H*P, single B/C group shared across heads (G=1).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig
from repro.common.scopes import scoped_kernel_vjp
from repro.models.layers import ParamSpec, rms_norm


def ssm_dims(cfg: ModelConfig) -> Tuple[int, int, int, int]:
    """(H, P, N, d_conv_channels)."""
    H = cfg.ssm_n_heads
    P = cfg.ssm_head_dim
    N = cfg.ssm_state
    return H, P, N, H * P + 2 * N


def ssm_spec(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    H, P, N, d_conv = ssm_dims(cfg)
    d_inner = H * P
    d = cfg.d_model
    return {
        "w_in": ParamSpec((d, 2 * d_inner + 2 * N + H), ("embed", "ssm_inner")),
        "conv_w": ParamSpec((cfg.ssm_conv_width, d_conv), ("conv", "ssm_inner"),
                            init="normal", scale=0.5),
        "conv_b": ParamSpec((d_conv,), ("ssm_inner",), init="zeros"),
        "A_log": ParamSpec((H,), ("ssm_heads",), init="zeros"),
        "dt_bias": ParamSpec((H,), ("ssm_heads",), init="zeros"),
        "D": ParamSpec((H,), ("ssm_heads",), init="ones"),
        "norm": ParamSpec((d_inner,), ("ssm_inner",), init="zeros"),
        "w_out": ParamSpec((d_inner, d), ("ssm_inner", "embed")),
    }


def _split_proj(zxbcdt: jax.Array, cfg: ModelConfig):
    H, P, N, _ = ssm_dims(cfg)
    d_inner = H * P
    z, xbc, dt = jnp.split(zxbcdt, [d_inner, 2 * d_inner + 2 * N], axis=-1)
    return z, xbc, dt  # (..., d_inner), (..., d_inner + 2N), (..., H)


def _causal_conv(xbc: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv over (B, S, C) with kernel (W, C)."""
    W = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    out = sum(pad[:, i : i + xbc.shape[1]] * w[i] for i in range(W))
    return jax.nn.silu(out + b)


def _discretize(dt_raw, A_log):
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32))          # (B, S, H)
    A = -jnp.exp(A_log.astype(jnp.float32))                   # (H,)
    return dt, dt * A                                          # dt, a = log-decay


def ssd_chunked(
    x: jax.Array,    # (B, S, H, P)
    dt: jax.Array,   # (B, S, H) fp32 (post-softplus)
    a: jax.Array,    # (B, S, H) fp32 log-decay (dt * A, negative)
    Bm: jax.Array,   # (B, S, N)
    Cm: jax.Array,   # (B, S, N)
    chunk: int,
    h0: jax.Array = None,  # (B, H, P, N) fp32 or None
) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD. Returns (y (B,S,H,P), h_final (B,H,P,N) fp32)."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    Sp = S + pad
    nc = Sp // Q

    def to_chunks(t, tail_shape):
        return jnp.moveaxis(t.reshape((B, nc, Q) + tail_shape), 1, 0)

    xc = to_chunks(x, (H, P))
    dtc = to_chunks(dt, (H,))
    ac = to_chunks(a, (H,))
    Bc = to_chunks(Bm, (N,))
    Cc = to_chunks(Cm, (N,))

    if h0 is None:
        h0 = jnp.zeros((B, H, P, N), jnp.float32)

    def body(h, inp):
        x_c, dt_c, a_c, B_c, C_c = inp          # (B,Q,H,P), (B,Q,H), ..., (B,Q,N)
        cum = jnp.cumsum(a_c, axis=1)           # (B,Q,H)
        # --- intra-chunk (quadratic within chunk; MXU matmuls) ---
        L = jnp.exp(cum[:, :, None, :] - cum[:, None, :, :])   # (B,Q,Q,H)
        tri = jnp.tril(jnp.ones((Q, Q), bool))
        L = jnp.where(tri[None, :, :, None], L, 0.0)
        CB = jnp.einsum("bqn,bkn->bqk", C_c.astype(jnp.float32),
                        B_c.astype(jnp.float32))               # (B,Q,Q)
        scores = CB[..., None] * L * dt_c[:, None, :, :]        # (B,Q,Q,H)
        y_intra = jnp.einsum("bqkh,bkhp->bqhp", scores, x_c.astype(jnp.float32))
        # --- contribution of the carried state ---
        y_inter = jnp.einsum(
            "bqn,bqh,bhpn->bqhp", C_c.astype(jnp.float32), jnp.exp(cum), h
        )
        # --- new carried state ---
        cum_last = cum[:, -1:, :]                               # (B,1,H)
        decay_to_end = jnp.exp(cum_last - cum) * dt_c           # (B,Q,H)
        state_new = jnp.einsum(
            "bkn,bkh,bkhp->bhpn", B_c.astype(jnp.float32), decay_to_end,
            x_c.astype(jnp.float32),
        )
        h = jnp.exp(cum_last[:, 0, :])[:, :, None, None] * h + state_new
        return h, (y_intra + y_inter).astype(x.dtype)

    def scanned(xc_, dtc_, ac_, Bc_, Cc_, h0_):
        return jax.lax.scan(body, h0_, (xc_, dtc_, ac_, Bc_, Cc_))

    core = scoped_kernel_vjp("fusedkernel_ssd_scan", scanned)
    h_final, yc = core(xc, dtc, ac, Bc, Cc, h0)
    y = jnp.moveaxis(yc, 0, 1).reshape(B, Sp, H, P)[:, :S]
    return y, h_final


def ssm_apply_train(
    p: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig
) -> jax.Array:
    """Full-sequence Mamba2 layer (train / prefill). x: (B, S, d) -> (B, S, d)."""
    H, P, N, _ = ssm_dims(cfg)
    B, S, d = x.shape
    z, xbc, dt_raw = _split_proj(x @ p["w_in"], cfg)
    xbc = _causal_conv(xbc, p["conv_w"], p["conv_b"])
    xs, Bm, Cm = jnp.split(xbc, [H * P, H * P + N], axis=-1)
    xs = xs.reshape(B, S, H, P)
    dt, a = _discretize(dt_raw, p["A_log"])
    y, _ = ssd_chunked(xs, dt, a, Bm, Cm, cfg.ssm_chunk)
    y = y + p["D"].astype(y.dtype)[None, None, :, None] * xs
    y = y.reshape(B, S, H * P)
    y = rms_norm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    return y @ p["w_out"]


def ssm_state_shapes(cfg: ModelConfig, batch: int, dtype) -> Dict[str, tuple]:
    H, P, N, d_conv = ssm_dims(cfg)
    return {
        "h": ((batch, H, P, N), jnp.float32),
        "conv": ((batch, cfg.ssm_conv_width - 1, d_conv), jnp.dtype(dtype)),
    }


def ssm_prefill(
    p: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Like train but also returns the decode state (h, conv tail)."""
    H, P, N, _ = ssm_dims(cfg)
    B, S, d = x.shape
    z, xbc_raw, dt_raw = _split_proj(x @ p["w_in"], cfg)
    xbc = _causal_conv(xbc_raw, p["conv_w"], p["conv_b"])
    xs, Bm, Cm = jnp.split(xbc, [H * P, H * P + N], axis=-1)
    xs = xs.reshape(B, S, H, P)
    dt, a = _discretize(dt_raw, p["A_log"])
    y, h = ssd_chunked(xs, dt, a, Bm, Cm, cfg.ssm_chunk)
    y = y + p["D"].astype(y.dtype)[None, None, :, None] * xs
    y = y.reshape(B, S, H * P)
    y = rms_norm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    W = cfg.ssm_conv_width
    conv_tail = xbc_raw[:, -(W - 1):, :] if S >= W - 1 else jnp.pad(
        xbc_raw, ((0, 0), (W - 1 - S, 0), (0, 0))
    )
    return y @ p["w_out"], {"h": h, "conv": conv_tail.astype(x.dtype)}


def ssm_decode_step(
    p: Dict[str, jax.Array],
    x: jax.Array,                     # (B, 1, d)
    state: Dict[str, jax.Array],      # {"h": (B,H,P,N) f32, "conv": (B,W-1,C)}
    cfg: ModelConfig,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    H, P, N, _ = ssm_dims(cfg)
    B = x.shape[0]
    z, xbc_raw, dt_raw = _split_proj(x @ p["w_in"], cfg)   # (B,1,*)
    window = jnp.concatenate([state["conv"], xbc_raw], axis=1)  # (B,W,C)
    conv_out = jnp.einsum("bwc,wc->bc", window.astype(jnp.float32),
                          p["conv_w"].astype(jnp.float32)) + p["conv_b"].astype(jnp.float32)
    xbc = jax.nn.silu(conv_out)[:, None, :].astype(x.dtype)     # (B,1,C)
    xs, Bm, Cm = jnp.split(xbc, [H * P, H * P + N], axis=-1)
    xs1 = xs.reshape(B, H, P)
    dt, a = _discretize(dt_raw[:, 0], p["A_log"])               # (B,H)
    h = state["h"]
    decay = jnp.exp(a)[:, :, None, None]                        # (B,H,1,1)
    inject = jnp.einsum(
        "bh,bhp,bn->bhpn", dt, xs1.astype(jnp.float32), Bm[:, 0].astype(jnp.float32)
    )
    h = decay * h + inject
    y = jnp.einsum("bhpn,bn->bhp", h, Cm[:, 0].astype(jnp.float32))
    y = y + p["D"].astype(jnp.float32)[None, :, None] * xs1.astype(jnp.float32)
    y = y.reshape(B, 1, H * P).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    new_state = {"h": h, "conv": window[:, 1:].astype(x.dtype)}
    return y @ p["w_out"], new_state


def ssd_reference(x, dt, a, Bm, Cm):
    """O(S·N·P) sequential oracle for tests: plain recurrence, no chunking."""
    B, S, H, P = x.shape

    def step(h, t):
        xt, dtt, at, Bt, Ct = t
        h = jnp.exp(at)[:, :, None, None] * h + jnp.einsum(
            "bh,bhp,bn->bhpn", dtt, xt, Bt
        )
        y = jnp.einsum("bhpn,bn->bhp", h, Ct)
        return h, y

    h0 = jnp.zeros((B, x.shape[2], P, Bm.shape[-1]), jnp.float32)
    xs = (
        jnp.moveaxis(x, 1, 0).astype(jnp.float32),
        jnp.moveaxis(dt, 1, 0),
        jnp.moveaxis(a, 1, 0),
        jnp.moveaxis(Bm, 1, 0).astype(jnp.float32),
        jnp.moveaxis(Cm, 1, 0).astype(jnp.float32),
    )
    h, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1), h
