"""Unified model facade: one API across all six architecture families.

``build_model(cfg)`` returns a :class:`Model` whose methods are pure functions
of (params, batch) suitable for jit/pjit:

* ``loss(params, batch, rt)``         — train objective (LM CE + MoE aux)
* ``prefill(params, batch, rt)``      — logits, last-layer hidden states, KV/SSM
                                        cache, aux (serving prefill path)
* ``decode_step(params, batch, cache, rt)`` — one new token vs. the cache
* ``init(key)/param_shapes()/cache_specs(...)`` — materialized or
  shape-only parameters and caches.

The ProD predictor head consumes ``hidden`` from prefill/decode — i.e. the
served model's last-layer hidden state, per the paper (§2.4).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig
from repro.models import encdec, transformer
from repro.models.layers import init_tree, shape_tree
from repro.models.transformer import Ctx


@dataclass(frozen=True)
class Runtime:
    """Execution-environment settings threaded through model calls: the
    device mesh (None on one device), rematerialization in training, and the
    MoE expert capacity factor (0 drops no token)."""

    mesh: Any = None
    remat: str = "none"
    capacity_factor: float = 1.25

    @staticmethod
    def local() -> "Runtime":
        return Runtime()

    def ctx(self, cfg: ModelConfig, mode: str) -> Ctx:
        return Ctx(
            cfg=cfg, mesh=self.mesh, mode=mode,
            remat=self.remat if mode == "train" else "none",
            capacity_factor=self.capacity_factor,
        )


def last_token_hidden(hidden: jax.Array, lengths: jax.Array) -> jax.Array:
    """φ(x): last-layer hidden state of the last (non-pad) prompt token."""
    idx = jnp.clip(lengths - 1, 0, hidden.shape[1] - 1)
    return hidden[jnp.arange(hidden.shape[0]), idx]


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # -- parameters ---------------------------------------------------------
    def spec(self):
        if self.cfg.family == "encdec":
            return encdec.whisper_spec(self.cfg)
        return transformer.model_spec(self.cfg)

    def init(self, key, dtype=None):
        return init_tree(key, self.spec(), dtype or self.cfg.dtype)

    def param_shapes(self, dtype=None):
        return shape_tree(self.spec(), dtype or self.cfg.dtype)

    # -- training -----------------------------------------------------------
    def loss(self, params, batch: Dict[str, jax.Array], rt: Runtime):
        cfg = self.cfg
        if cfg.family == "encdec":
            return encdec.whisper_loss(
                params, cfg, batch["tokens"], batch["enc_embeds"],
                loss_mask=batch.get("loss_mask"),
            )
        return transformer.lm_loss(
            params, cfg, batch.get("tokens"), loss_mask=batch.get("loss_mask"),
            ctx=rt.ctx(cfg, "train"), embeds=batch.get("embeds"),
            positions=batch.get("positions"),
        )

    # -- serving ------------------------------------------------------------
    def prefill(self, params, batch: Dict[str, jax.Array], rt: Runtime,
                logits_mode: str = "all"):
        """Returns (logits, hidden (B,S,d), cache, aux)."""
        cfg = self.cfg
        if cfg.family == "encdec":
            enc_out = encdec.encode(params, cfg, batch["enc_embeds"])
            logits, hidden, cache = encdec.decoder_forward(
                params, cfg, batch["tokens"], enc_out, mode="prefill",
                attn_valid=batch.get("attn_valid"), logits_mode=logits_mode,
            )
            return logits, hidden, cache, jnp.zeros((), jnp.float32)
        logits, hidden, cache, aux = transformer.forward(
            params, cfg, tokens=batch.get("tokens"), embeds=batch.get("embeds"),
            positions=batch.get("positions"), attn_valid=batch.get("attn_valid"),
            ctx=rt.ctx(cfg, "prefill"), logits_mode=logits_mode,
        )
        return logits, hidden, cache, aux

    def decode_step(self, params, batch: Dict[str, jax.Array], cache, rt: Runtime):
        """batch: tokens (B,), pos (B,), lengths (B,). Returns (logits, hidden, cache)."""
        cfg = self.cfg
        if cfg.family == "encdec":
            return encdec.decoder_decode_step(
                params, cfg, batch["tokens"], cache, batch["pos"], batch["lengths"]
            )
        logits, hidden, new_cache, _ = transformer.decode_step(
            params, cfg, batch["tokens"], cache, batch["pos"], batch["lengths"],
            ctx=rt.ctx(cfg, "decode"), embeds=batch.get("embeds"),
        )
        return logits, hidden, new_cache

    # -- caches --------------------------------------------------------------
    def cache_specs(self, batch: int, cache_len: int):
        if self.cfg.family == "encdec":
            return encdec.decoder_cache_spec(self.cfg, batch, cache_len)
        return transformer.cache_spec(self.cfg, batch, cache_len)

    def cache_shapes(self, batch: int, cache_len: int, dtype=None):
        dt = dtype or self.cfg.dtype
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, transformer.cache_dtype(s, dt)),
            self.cache_specs(batch, cache_len),
            is_leaf=lambda x: hasattr(x, "axes") and hasattr(x, "init"),
        )


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg=cfg)
