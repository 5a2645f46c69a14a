"""Decoder-only transformer assembly for dense / MoE / SSM / hybrid / VLM.

Architectures are described by a *layer plan*: a list of segments, each a
``(kinds, n_blocks)`` pair scanned with ``lax.scan`` over stacked block
parameters. Within a block the (static, short) ``kinds`` tuple is unrolled:

* uniform dense:   [ (('full',), L) ]
* gemma3 5:1:      [ (('local',)*5 + ('full',), L//6), (('local',)*(L%6), 1) ]
* MoE:             [ (('moe',), L) ]
* mamba2:          [ (('ssm',), L) ]
* zamba2 hybrid:   [ (('shared_attn',) + ('ssm',)*k, L//k), (('ssm',)*(L%k), 1) ]

``shared_attn`` uses one weight-shared attention+MLP block (Zamba2) passed via
closure, while its KV cache *is* per-invocation (scanned).

Modes: ``forward`` (train), ``prefill`` (returns KV/SSM caches + last hidden
states for the ProD predictor), ``decode_step`` (one token, static shapes).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig
from repro.kernels import ops as kops
from repro.models import attention as attn
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    ParamSpec,
    embed_spec,
    init_tree,
    mlp_apply,
    mlp_spec,
    rms_norm,
    shape_tree,
    stack_specs,
    unembed,
)
from repro.models.moe import moe_apply, moe_spec
from repro.models.rope import rope_angles, positions_from_tokens, text_mrope_positions


# ---------------------------------------------------------------------------
# layer plan
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Segment:
    kinds: Tuple[str, ...]
    n_blocks: int


def layer_plan(cfg: ModelConfig) -> List[Segment]:
    L = cfg.n_layers
    if cfg.family in ("dense", "vlm", "moe"):
        if cfg.local_global_ratio > 0:
            blk = ("local",) * cfg.local_global_ratio + ("full",)
            segs = []
            if L // len(blk):
                segs.append(Segment(blk, L // len(blk)))
            rem = L % len(blk)
            if rem:
                segs.append(Segment(("local",) * rem, 1))
            return segs
        kind = "moe" if cfg.family == "moe" else ("local" if cfg.attn_window else "full")
        return [Segment((kind,), L)]
    if cfg.family == "ssm":
        return [Segment(("ssm",), L)]
    if cfg.family == "hybrid":
        k = max(cfg.attn_every, 1)
        segs = []
        if L // k:
            segs.append(Segment(("shared_attn",) + ("ssm",) * k, L // k))
        if L % k:
            segs.append(Segment(("ssm",) * (L % k), 1))
        return segs
    raise ValueError(cfg.family)


def _attn_kind_window(cfg: ModelConfig, kind: str) -> int:
    if kind == "local":
        return cfg.attn_window
    if kind == "shared_attn":
        return cfg.attn_window  # zamba2 shared block rings at long context
    return 0


# ---------------------------------------------------------------------------
# parameter spec
# ---------------------------------------------------------------------------


def _layer_spec(cfg: ModelConfig, kind: str):
    norm = lambda: ParamSpec((cfg.d_model,), ("embed",), init="zeros")
    if kind in ("full", "local"):
        return {"ln1": norm(), "attn": attn.attn_spec(cfg), "ln2": norm(),
                "mlp": mlp_spec(cfg.d_model, cfg.d_ff, cfg.act)}
    if kind == "moe":
        return {"ln1": norm(), "attn": attn.attn_spec(cfg), "ln2": norm(),
                "moe": moe_spec(cfg)}
    if kind == "ssm":
        return {"ln1": norm(), "ssm": ssm_mod.ssm_spec(cfg)}
    if kind == "shared_attn":
        return {}  # weights live in params["shared"], applied by closure
    raise ValueError(kind)


def shared_block_spec(cfg: ModelConfig):
    norm = lambda: ParamSpec((cfg.d_model,), ("embed",), init="zeros")
    return {"ln1": norm(), "attn": attn.attn_spec(cfg), "ln2": norm(),
            "mlp": mlp_spec(cfg.d_model, cfg.d_ff, "silu")}


def model_spec(cfg: ModelConfig) -> Dict[str, Any]:
    plan = layer_plan(cfg)
    spec: Dict[str, Any] = {"embed": embed_spec(cfg.vocab_size, cfg.d_model)}
    spec["final_norm"] = ParamSpec((cfg.d_model,), ("embed",), init="zeros")
    if not cfg.tie_embeddings:
        spec["head"] = ParamSpec((cfg.d_model, cfg.vocab_size), ("embed", "vocab"))
    segs = []
    for seg in plan:
        block = {f"layer_{j}": _layer_spec(cfg, kind) for j, kind in enumerate(seg.kinds)}
        segs.append(stack_specs(block, seg.n_blocks))
    spec["segments"] = segs
    if cfg.family == "hybrid":
        spec["shared"] = shared_block_spec(cfg)
    return spec


# ---------------------------------------------------------------------------
# forward context
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Ctx:
    cfg: ModelConfig
    mesh: Any = None
    mode: str = "train"              # train | prefill | decode
    remat: str = "none"              # none | full
    capacity_factor: float = 1.25


def _angles_for(cfg: ModelConfig, positions, kind: str):
    theta = cfg.rope_theta_local if kind == "local" else cfg.rope_theta
    return rope_angles(positions, cfg.head_dim, theta, use_mrope=cfg.use_mrope)


def _precompute_angles(cfg: ModelConfig, plan, positions):
    """RoPE angle tables per rope-base, computed OUTSIDE layer scans (a cached
    tracer from one scan body must never leak into another)."""
    keys = set()
    for seg in plan:
        for kind in seg.kinds:
            if kind != "ssm":
                keys.add("local" if kind == "local" else "global")
    return {k: _angles_for(cfg, positions, k) for k in keys} or {"global": None}


# ---------------------------------------------------------------------------
# single-layer application (full sequence: train / prefill)
# ---------------------------------------------------------------------------


def _apply_attn_seq(lp, x, ctx: Ctx, kind: str, angles, attn_valid, lengths):
    cfg = ctx.cfg
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    q, k, v = attn.qkv_project(lp["attn"], h, cfg, angles)
    window = _attn_kind_window(cfg, kind)
    if ctx.mode == "prefill" and kops.resolve("auto") != "xla":
        # on TPU, the Pallas kernel: only the blocks the causal mask and each
        # row's length leave are computed
        o = kops.flash_attention(q, k, v, lengths, window=window, impl="auto")
    else:
        # training, the encoder-decoder and, off TPU, the prefill (the host's
        # tests): blocked XLA attention, with a backward pass and
        # a working set of blocks rather than (S, S) scores
        o = attn.blocked_attention(q, k, v, causal=True, window=window,
                                   kv_valid=attn_valid)
    x = x + attn.out_project(lp["attn"], o)
    return x, (k, v)


def _apply_ffn(lp, x, ctx: Ctx, ffn_kind: str):
    cfg = ctx.cfg
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    if ffn_kind == "moe":
        y, aux = moe_apply(lp["moe"], h, cfg, mesh=ctx.mesh,
                           capacity_factor=ctx.capacity_factor)
    else:
        y = mlp_apply(lp["mlp"], h, cfg.act)
    return x + y, aux


def _apply_layer_seq(lp, shared, x, ctx: Ctx, kind: str, angles, attn_valid, lengths):
    """Returns (x, cache_entry, aux)."""
    cfg = ctx.cfg
    if kind == "ssm":
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        if ctx.mode == "prefill":
            y, state = ssm_mod.ssm_prefill(lp["ssm"], h, cfg)
            return x + y, state, jnp.zeros((), jnp.float32)
        y = ssm_mod.ssm_apply_train(lp["ssm"], h, cfg)
        return x + y, None, jnp.zeros((), jnp.float32)
    p = shared if kind == "shared_attn" else lp
    x, (k, v) = _apply_attn_seq(p, x, ctx, kind, angles, attn_valid, lengths)
    x, aux = _apply_ffn(p, x, ctx, "moe" if kind == "moe" else "mlp")
    cache = None
    if ctx.mode == "prefill":
        W = _attn_kind_window(cfg, kind)
        k, v = attn.kv_lanes(k), attn.kv_lanes(v)
        cache = _ring_from_prefill(k, v, W) if W else {"k": k, "v": v}
    return x, cache, aux


def _ring_from_prefill(k, v, W: int):
    B, S, C = k.shape
    if S <= W:
        pad = W - S
        kr = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        vr = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
        # token t sits at slot t % W == t for t < S — already aligned
        return {"k": kr, "v": vr}
    t = jnp.arange(S - W, S, dtype=jnp.int32)
    slots = jnp.mod(t, W)
    kr = jnp.zeros((B, W, C), k.dtype).at[:, slots].set(k[:, S - W :])
    vr = jnp.zeros((B, W, C), v.dtype).at[:, slots].set(v[:, S - W :])
    return {"k": kr, "v": vr}


# ---------------------------------------------------------------------------
# full-sequence pass (train / prefill)
# ---------------------------------------------------------------------------


def forward(
    params: Dict[str, Any],
    cfg: ModelConfig,
    tokens: Optional[jax.Array] = None,        # (B, S) int32
    embeds: Optional[jax.Array] = None,        # (B, S, d) — VLM / audio stubs
    positions: Optional[jax.Array] = None,     # (B, S) or (3, B, S) for M-RoPE
    attn_valid: Optional[jax.Array] = None,    # (B, S) bool
    ctx: Optional[Ctx] = None,
    logits_mode: str = "all",                  # all | none (serving prefill)
):
    """Full-sequence pass. Returns (logits, hidden, cache_or_None, aux_loss).

    ``logits_mode="none"`` skips the (B, S, V) unembed entirely — serving
    prefill gathers the last-token hidden state and unembeds (B, V) itself.
    A prefill's rows are right-padded: ``attn_valid`` marks each row's
    prompt, which attention reads as the row's length.
    """
    ctx = ctx or Ctx(cfg=cfg)
    if embeds is None:
        embeds = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    B, S = embeds.shape[:2]
    if positions is None:
        positions = (
            text_mrope_positions(B, S) if cfg.use_mrope else positions_from_tokens(B, S)
        )
    lengths = None
    if ctx.mode == "prefill" and attn_valid is not None:
        lengths = jnp.sum(attn_valid, axis=-1, dtype=jnp.int32)
    plan = layer_plan(cfg)
    angle_map = _precompute_angles(cfg, plan, positions)
    angles = lambda kind: angle_map["local" if kind == "local" else "global"]

    shared = params.get("shared")
    x = embeds
    aux_total = jnp.zeros((), jnp.float32)
    caches = []
    for seg, seg_params in zip(plan, params["segments"]):

        def block_fn(carry, block_p, _kinds=seg.kinds):
            x, aux = carry
            entries = {}
            for j, kind in enumerate(_kinds):
                lp = block_p[f"layer_{j}"]
                x, cache, a = _apply_layer_seq(
                    lp, shared, x, ctx, kind, angles(kind), attn_valid, lengths
                )
                if ctx.mode == "prefill":
                    entries[f"layer_{j}"] = cache
                aux = aux + a
            return (x, aux), entries

        fn = jax.checkpoint(block_fn) if ctx.remat == "full" else block_fn
        (x, aux_total), seg_cache = jax.lax.scan(fn, (x, aux_total), seg_params)
        caches.append(seg_cache)

    hidden = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (unembed(hidden, params["embed"], params.get("head"))
              if logits_mode == "all" else None)
    cache = caches if ctx.mode == "prefill" else None
    return logits, hidden, cache, aux_total


# ---------------------------------------------------------------------------
# decode step
# ---------------------------------------------------------------------------


def cache_spec(cfg: ModelConfig, batch: int, cache_len: int):
    """ParamSpec pytree (shapes + logical axes) for the decode cache.

    Windowed layers allocate a ring of ``min(window, cache_len)``; full layers
    allocate ``cache_len``. K/V are (layers, batch, seq, KV·hd): heads and
    head width merged, lane-dense for every head width. SSM layers carry
    (h, conv) state.
    """
    plan = layer_plan(cfg)
    H, P, N, d_conv = (0, 0, 0, 0)
    if cfg.family in ("ssm", "hybrid"):
        H, P, N, d_conv = ssm_mod.ssm_dims(cfg)
    kv_ax = ("layers", "batch", "cache_seq", "cache_kv_heads")
    segs = []
    for seg in plan:
        entries = {}
        for j, kind in enumerate(seg.kinds):
            n = seg.n_blocks
            if kind == "ssm":
                entries[f"layer_{j}"] = {
                    "h": ParamSpec((n, batch, H, P, N),
                                   ("layers", "batch", "ssm_heads", None, None)),
                    "conv": ParamSpec((n, batch, cfg.ssm_conv_width - 1, d_conv),
                                      ("layers", "batch", None, "ssm_inner")),
                }
            else:
                W = _attn_kind_window(cfg, kind)
                Sc = min(W, cache_len) if W else cache_len
                kv_shape = (n, batch, Sc, cfg.n_kv_heads * cfg.head_dim)
                entries[f"layer_{j}"] = {"k": ParamSpec(kv_shape, kv_ax),
                                         "v": ParamSpec(kv_shape, kv_ax)}
        segs.append(entries)
    return segs


def cache_dtype(spec: ParamSpec, dtype):
    """SSM h-state fp32; model dtype else."""
    if spec.axes[2:3] == ("ssm_heads",):
        return jnp.float32
    return jnp.dtype(dtype)


def _decode_attn_layer(lp, x, entry, i, ctx: Ctx, kind: str, pos, lengths, angles):
    """One cached attention layer for a single new token.

    ``entry`` is the segment's stacked cache for this layer position,
    (n_blocks, B, Sc, ·); the token's K/V are written into
    it in place at ``[i, row, slot]``, then block ``i``'s slice is read for
    attention. Returns (x, the updated stacked entry)."""
    cfg = ctx.cfg
    B = x.shape[0]
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    q, k, v = attn.qkv_project(lp["attn"], h, cfg, angles)  # (B,1,·,hd)
    W = _attn_kind_window(cfg, kind)
    Sc = entry["k"].shape[2]
    ring = bool(W) and Sc == W  # ring iff allocated exactly the window
    bidx = jnp.arange(B)
    k_new, v_new = k[:, 0], v[:, 0]
    slot = jnp.mod(pos, Sc) if ring else pos
    new_entry = {
        "k": entry["k"].at[i, bidx, slot].set(attn.kv_lanes(k_new).astype(entry["k"].dtype)),
        "v": entry["v"].at[i, bidx, slot].set(attn.kv_lanes(v_new).astype(entry["v"].dtype)),
    }
    kc, vc = new_entry["k"][i], new_entry["v"][i]
    if ring:
        valid = attn.ring_cache_valid(lengths, Sc)
    else:
        valid = attn.full_cache_valid(lengths, Sc)
        if W:  # windowed semantics on a full cache
            kpos = jnp.arange(Sc, dtype=jnp.int32)[None, :]
            valid = valid & ((pos[:, None] - kpos) < W)
    o = attn.decode_attention(q, kc, vc, valid)
    x = x + attn.out_project(lp["attn"], o)
    return x, new_entry


def decode_step(
    params: Dict[str, Any],
    cfg: ModelConfig,
    tokens: jax.Array,                 # (B,) int32 — the new token
    cache: Any,                        # pytree of cache_spec's shapes
    pos: jax.Array,                    # (B,) int32 — position of the new token
    lengths: jax.Array,                # (B,) int32 — length AFTER this token
    ctx: Optional[Ctx] = None,
    embeds: Optional[jax.Array] = None,
):
    """One decode step. Returns (logits (B, V), hidden (B, d), new_cache, aux).

    Each segment's stacked cache is carried through the layer scan as loop
    state and updated in place, so a caller that donates ``cache`` holds one
    cache, not an input and an output."""
    ctx = ctx or Ctx(cfg=cfg, mode="decode")
    B = tokens.shape[0]
    if embeds is None:
        x = jnp.take(params["embed"], tokens[:, None], axis=0).astype(cfg.dtype)
    else:
        x = embeds[:, None] if embeds.ndim == 2 else embeds
    if cfg.use_mrope:
        positions = jnp.broadcast_to(pos[None, :, None], (3, B, 1))
    else:
        positions = pos[:, None]
    plan = layer_plan(cfg)
    angle_map = _precompute_angles(cfg, plan, positions)
    angles = lambda kind: angle_map["local" if kind == "local" else "global"]

    shared = params.get("shared")
    aux_total = jnp.zeros((), jnp.float32)
    new_cache = []
    for seg, seg_params, seg_cache in zip(plan, params["segments"], cache):

        def block_fn(carry, xs, _kinds=seg.kinds):
            x, aux, c = carry
            block_p, i = xs
            c = dict(c)
            for j, kind in enumerate(_kinds):
                name = f"layer_{j}"
                lp = block_p[name]
                if kind == "ssm":
                    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
                    st = jax.tree_util.tree_map(lambda a: a[i], c[name])
                    y, st = ssm_mod.ssm_decode_step(lp["ssm"], h, st, cfg)
                    x = x + y
                    c[name] = jax.tree_util.tree_map(lambda a, s: a.at[i].set(s),
                                                     c[name], st)
                else:
                    p = shared if kind == "shared_attn" else lp
                    x, c[name] = _decode_attn_layer(p, x, c[name], i, ctx, kind, pos,
                                                    lengths, angles(kind))
                    x, a = _apply_ffn(p, x, ctx, "moe" if kind == "moe" else "mlp")
                    aux = aux + a
            return (x, aux, c), None

        (x, aux_total, seg_new), _ = jax.lax.scan(
            block_fn, (x, aux_total, seg_cache),
            (seg_params, jnp.arange(seg.n_blocks, dtype=jnp.int32)))
        new_cache.append(seg_new)

    hidden = rms_norm(x[:, 0], params["final_norm"], cfg.norm_eps)
    logits = unembed(hidden, params["embed"], params.get("head"))
    return logits, hidden, new_cache, aux_total


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------


def lm_loss(
    params, cfg: ModelConfig, tokens, loss_mask=None, ctx: Optional[Ctx] = None,
    embeds=None, positions=None,
):
    """Next-token cross-entropy (+ MoE aux). Returns (loss, metrics)."""
    logits, _, _, aux = forward(
        params, cfg, tokens=tokens, embeds=embeds, positions=positions, ctx=ctx
    )
    targets = tokens[:, 1:]
    lg = logits[:, :-1]
    logp = jax.nn.log_softmax(lg, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if loss_mask is not None:
        m = loss_mask[:, 1:].astype(jnp.float32)
        loss = jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
    else:
        loss = jnp.mean(nll)
    total = loss + cfg.router_aux_coef * aux
    return total, {"loss": loss, "aux": aux}
