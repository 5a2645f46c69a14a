"""Mixture-of-Experts FFN with capacity-based dispatch and expert parallelism.

Design notes (TPU adaptation):

* **Capacity dispatch** (GShard/Switch style): tokens are sorted by expert id
  and gathered into a static ``(E_local, capacity, d)`` buffer, so the expert
  matmuls are plain batched einsums. This keeps HLO FLOPs proportional to
  *active* compute (``jax.lax.ragged_dot`` is counted by XLA as dense over all
  experts — a 384× overcount for kimi-k2 — which would poison the roofline).
* **Expert parallelism**: experts are sharded over the ``model`` mesh axis via
  ``shard_map``; activations arrive replicated across that axis (they are
  sharded over ``data`` only), each model column computes its local experts,
  and a single ``psum`` over ``model`` combines — the collective cost is one
  all-reduce of the activation block per MoE layer. The router is replicated
  (its weights are tiny) so no all-gather of logits is needed.
* Overflow beyond capacity is dropped (standard); tests use a capacity factor
  that provably avoids drops so the oracle comparison is exact.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.common.config import ModelConfig
from repro.models.layers import ParamSpec, mlp_spec, mlp_apply


def moe_spec(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    E, d, f = cfg.n_experts, cfg.d_model, cfg.moe_d_ff
    s = {
        "router": ParamSpec((d, E), ("embed", "experts"), scale=0.02),
        # expert weights are too large to replicate over `data` (kimi: 2 TB
        # bf16): stored d-sharded over data ("embed_fsdp") + expert-sharded
        # over model, and explicitly all-gathered inside the shard_map
        "w_gate": ParamSpec((E, d, f), ("experts", "embed_fsdp", "expert_ffn")),
        "w_up": ParamSpec((E, d, f), ("experts", "embed_fsdp", "expert_ffn")),
        "w_down": ParamSpec((E, f, d), ("experts", "expert_ffn", "embed_fsdp")),
    }
    if cfg.n_shared_experts:
        s["shared"] = mlp_spec(d, cfg.moe_d_ff * cfg.n_shared_experts, "silu")
    return s


def _router(logits32: jax.Array, k: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k routing. Returns (expert_ids (T,k), combine_w (T,k), aux_loss)."""
    T, E = logits32.shape
    probs = jax.nn.softmax(logits32, axis=-1)
    top_p, top_ids = jax.lax.top_k(probs, k)
    combine = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)
    # Switch-style load-balance auxiliary loss
    frac_tokens = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_ids, E, dtype=jnp.float32), axis=1), axis=0
    )  # (E,) expected assignments per token
    mean_prob = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens / k * mean_prob)
    return top_ids, combine, aux


def _capacity(T: int, k: int, E_local: int, factor: float) -> int:
    cap = int(T * k * factor / max(E_local, 1)) + 1
    return max(8, min(cap, T * k))


def _dispatch(local_ids: jax.Array, combine_w: jax.Array, E_local: int,
              capacity: int):
    """Sort-based capacity dispatch bookkeeping (no data movement).

    Returns (gather_tok (E_local, cap) token index per expert slot,
    valid (E_local, cap), weight (E_local, cap))."""
    T, k = local_ids.shape
    Tk = T * k
    flat_ids = local_ids.reshape(Tk)
    order = jnp.argsort(flat_ids)                      # stable; overflow ids last
    tok_of_sorted = order // k                         # token index per sorted row
    w_sorted = combine_w.reshape(Tk)[order]
    counts = jnp.zeros(E_local + 1, jnp.int32).at[flat_ids].add(1)[:E_local]
    offsets = jnp.cumsum(counts) - counts              # (E_local,)
    idx = offsets[:, None] + jnp.arange(capacity, dtype=jnp.int32)[None, :]
    valid = jnp.arange(capacity, dtype=jnp.int32)[None, :] < counts[:, None]
    idx_c = jnp.where(valid, idx, Tk - 1)
    return tok_of_sorted[idx_c], valid, w_sorted[idx_c] * valid


def _expert_mats(xe, w_gate, w_up, w_down):
    h = jax.nn.silu(
        jnp.einsum("ecd,edf->ecf", xe, w_gate)
    ) * jnp.einsum("ecd,edf->ecf", xe, w_up)
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def _combine(ye, gather_tok, valid, weight, T: int, d: int):
    ye = ye * weight[..., None].astype(ye.dtype)
    out_tok = jnp.where(valid, gather_tok, T)           # invalid -> drop bucket
    out = jnp.zeros((T + 1, d), ye.dtype).at[out_tok.reshape(-1)].add(
        ye.reshape(-1, d))
    return out[:T]


def _expert_ffn_local(
    x: jax.Array,            # (T, d)
    local_ids: jax.Array,    # (T, k) in [0, E_local]; E_local == "not mine"
    combine_w: jax.Array,    # (T, k)
    w_gate: jax.Array,       # (E_local, d, f)
    w_up: jax.Array,
    w_down: jax.Array,       # (E_local, f, d)
    capacity: int,
) -> jax.Array:
    """Capacity-dispatch expert computation on one shard. Returns (T, d)."""
    T = x.shape[0]
    E_local = w_gate.shape[0]
    gather_tok, valid, weight = _dispatch(local_ids, combine_w, E_local, capacity)
    xe = jnp.take(x, gather_tok, axis=0)               # (E_local, cap, d)
    xe = jnp.where(valid[..., None], xe, 0)
    ye = _expert_mats(xe, w_gate, w_up, w_down)
    return _combine(ye, gather_tok, valid, weight, T, x.shape[1])


def moe_apply(
    p: Dict[str, jax.Array],
    x: jax.Array,             # (B, S, d)
    cfg: ModelConfig,
    mesh=None,
    capacity_factor: float = 1.25,
    batch_spec: Optional[P] = None,
    cap_slack: float = 2.0,
    fsdp_mode: str = "gather",    # gather | partial (see Runtime docstring)
) -> Tuple[jax.Array, jax.Array]:
    """MoE FFN. Returns (out (B,S,d), aux_loss scalar)."""
    B, S, d = x.shape
    T = B * S
    k = cfg.n_experts_per_token
    E = cfg.n_experts
    xf = x.reshape(T, d)
    logits32 = (xf.astype(jnp.float32)) @ (p["router"].astype(jnp.float32))
    top_ids, combine, aux = _router(logits32, k)
    cf = capacity_factor if capacity_factor else float(E)  # 0 -> no-drop

    ep = mesh is not None and "model" in mesh.axis_names and mesh.shape["model"] > 1 \
        and E % mesh.shape["model"] == 0
    if not ep:
        cap = _capacity(T, k, E, cf)
        out = _expert_ffn_local(
            xf, top_ids, combine, p["w_gate"], p["w_up"], p["w_down"], cap
        ).reshape(B, S, d)
    else:
        n_shards = mesh.shape["model"]
        E_local = E // n_shards
        data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        fsdp = tuple(a for a in data_axes if mesh.shape[a] > 1)
        n_data = 1
        for a in data_axes:
            n_data *= int(mesh.shape[a])
        if batch_spec is not None:
            xspec = batch_spec
            tokens_sharded = True
        elif T % max(n_data, 1) == 0 and n_data > 1:
            xspec = P(data_axes, None)
            tokens_sharded = True
        else:
            xspec = P(None, None)  # decode batch=1: tokens replicated
            tokens_sharded = False
        # capacity is per SHARD-LOCAL tokens (computing it on the global T
        # over-allocated the expert matmuls 16x — see EXPERIMENTS.md §Perf)
        T_loc = T // n_data if tokens_sharded else T
        cap = int(_capacity(T_loc, k, E, cf) * cap_slack)
        cap = max(8, min(cap, T_loc * k))
        # expert weights stored d-sharded over the data axes (FSDP) and
        # expert-sharded over model; gathered per layer inside the shard_map
        wspec_up = P("model", fsdp if fsdp else None, None)
        wspec_dn = P("model", None, fsdp if fsdp else None)

        def _local_ids(ids):
            lo = jax.lax.axis_index("model") * E_local
            return jnp.where((ids >= lo) & (ids < lo + E_local), ids - lo, E_local)

        def shard_fn(xf_l, ids_l, cw_l, wg_l, wu_l, wd_l):
            """FSDP mode "gather": all-gather the d-sharded expert weights per
            layer (train-friendly: weight traffic amortized over many tokens)."""
            if fsdp:
                wg_l = jax.lax.all_gather(wg_l, fsdp, axis=1, tiled=True)
                wu_l = jax.lax.all_gather(wu_l, fsdp, axis=1, tiled=True)
                wd_l = jax.lax.all_gather(wd_l, fsdp, axis=2, tiled=True)
            out_l = _expert_ffn_local(xf_l, _local_ids(ids_l), cw_l,
                                      wg_l, wu_l, wd_l, cap)
            return jax.lax.psum(out_l, "model")

        def shard_fn_partial(xf_l, ids_l, cw_l, wg_l, wu_l, wd_l):
            """FSDP mode "partial": weights stay d-sharded; tokens are gathered
            over the data axes (tiny at decode), pre-activations are partial-
            summed. Weight traffic: ZERO; activation traffic ~ O(tokens×f).
            The decode-friendly choice (weights ≫ activations)."""
            n_fsdp = 1
            didx = jnp.zeros((), jnp.int32)
            for a in fsdp:
                n_fsdp *= int(mesh.shape[a])
                didx = didx * int(mesh.shape[a]) + jax.lax.axis_index(a)
            T_l = xf_l.shape[0]
            if fsdp:
                x_g = jax.lax.all_gather(xf_l, fsdp, axis=0, tiled=True)
                ids_g = jax.lax.all_gather(ids_l, fsdp, axis=0, tiled=True)
                cw_g = jax.lax.all_gather(cw_l, fsdp, axis=0, tiled=True)
            else:
                x_g, ids_g, cw_g = xf_l, ids_l, cw_l
            T_g = x_g.shape[0]
            d_l = d // n_fsdp
            x_slice = jax.lax.dynamic_slice_in_dim(x_g, didx * d_l, d_l, 1)
            cap_g = max(8, min(int(_capacity(T_g, k, E, cf) * cap_slack),
                               T_g * k))
            gtok, valid, weight = _dispatch(_local_ids(ids_g), cw_g, E_local,
                                            cap_g)
            xe = jnp.where(valid[..., None], jnp.take(x_slice, gtok, axis=0), 0)
            pre_g = jax.lax.psum(jnp.einsum("ecd,edf->ecf", xe, wg_l), fsdp) \
                if fsdp else jnp.einsum("ecd,edf->ecf", xe, wg_l)
            pre_u = jax.lax.psum(jnp.einsum("ecd,edf->ecf", xe, wu_l), fsdp) \
                if fsdp else jnp.einsum("ecd,edf->ecf", xe, wu_l)
            h = jax.nn.silu(pre_g) * pre_u
            ye = jnp.einsum("ecf,efd->ecd", h, wd_l)       # (E_l, cap_g, d_l)
            out_g = _combine(ye, gtok, valid, weight, T_g, d_l)  # (T_g, d_l)
            if fsdp:
                out_full = jax.lax.all_gather(out_g, fsdp, axis=1, tiled=True)
                out_l = jax.lax.dynamic_slice_in_dim(out_full, didx * T_l, T_l, 0)
            else:
                out_l = out_g
            return jax.lax.psum(out_l, "model")

        fn = shard_fn_partial if fsdp_mode == "partial" else shard_fn
        out = jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=(xspec, xspec, xspec, wspec_up, wspec_up, wspec_dn),
            out_specs=xspec,
            check_vma=False,
        )(xf, top_ids, combine, p["w_gate"], p["w_up"], p["w_down"]).reshape(B, S, d)

    if cfg.n_shared_experts and "shared" in p:
        out = out + mlp_apply(p["shared"], x, "silu")
    return out, aux.astype(jnp.float32)
