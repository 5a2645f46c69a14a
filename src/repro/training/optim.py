"""Pure-JAX optimizers and LR schedules (no optax dependency).

* AdamW — standard decoupled weight decay.
* Adafactor — factored second moment (rank-1 row/col stats for matrices);
  the memory-frugal choice for the largest (1T MoE) train config.
* Schedules — cosine, constant, and **WSD** (warmup–stable–decay), the
  MiniCPM schedule [arXiv:2404.06395].
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.common.config import TrainConfig


# ---------------------------------------------------------------------------
# schedules
# ---------------------------------------------------------------------------


def lr_schedule(cfg: TrainConfig) -> Callable[[jax.Array], jax.Array]:
    peak = cfg.lr
    warm = max(cfg.warmup_steps, 1)

    def cosine(step):
        frac = jnp.clip((step - warm) / max(cfg.decay_steps - warm, 1), 0.0, 1.0)
        return peak * jnp.where(
            step < warm, step / warm, 0.5 * (1 + jnp.cos(jnp.pi * frac))
        )

    def constant(step):
        return peak * jnp.minimum(step / warm, 1.0)

    def wsd(step):
        """Warmup -> stable plateau -> exponential-ish decay (MiniCPM)."""
        stable_end = warm + cfg.stable_steps
        decay_len = max(cfg.decay_steps - stable_end, 1)
        frac = jnp.clip((step - stable_end) / decay_len, 0.0, 1.0)
        return peak * jnp.where(
            step < warm,
            step / warm,
            jnp.where(step < stable_end, 1.0, 0.5 ** (frac * 10.0)),
        )

    return {"cosine": cosine, "constant": constant, "wsd": wsd}[cfg.schedule]


# ---------------------------------------------------------------------------
# gradient utilities
# ---------------------------------------------------------------------------


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
            for x in jax.tree_util.tree_leaves(tree))
    )


def clip_by_global_norm(grads, max_norm: float):
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree_util.tree_map(lambda g: g * scale, grads), norm


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Optimizer:
    init: Callable[[Any], Any]
    update: Callable[[Any, Any, Any, jax.Array], Tuple[Any, Any]]
    # update(grads, state, params, step) -> (new_params, new_state)


def adamw(cfg: TrainConfig) -> Optimizer:
    sched = lr_schedule(cfg)
    b1, b2, eps, wd = cfg.beta1, cfg.beta2, 1e-8, cfg.weight_decay

    def init(params):
        z = lambda p: jnp.zeros(p.shape, jnp.float32)
        return {
            "m": jax.tree_util.tree_map(z, params),
            "v": jax.tree_util.tree_map(z, params),
        }

    def update(grads, state, params, step):
        lr = sched(step + 1)
        t = (step + 1).astype(jnp.float32)
        bc1 = 1 - b1 ** t
        bc2 = 1 - b2 ** t

        def upd(g, m, v, p):
            g32 = g.astype(jnp.float32)
            m = b1 * m + (1 - b1) * g32
            v = b2 * v + (1 - b2) * jnp.square(g32)
            step_ = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
            decay = wd * p.astype(jnp.float32) if p.ndim >= 2 else 0.0
            newp = p.astype(jnp.float32) - lr * (step_ + decay)
            return newp.astype(p.dtype), m, v

        flat_p, treedef = jax.tree_util.tree_flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_m = treedef.flatten_up_to(state["m"])
        flat_v = treedef.flatten_up_to(state["v"])
        out = [upd(g, m, v, p) for g, m, v, p in zip(flat_g, flat_m, flat_v, flat_p)]
        new_p = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
        new_m = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
        new_v = jax.tree_util.tree_unflatten(treedef, [o[2] for o in out])
        return new_p, {"m": new_m, "v": new_v}

    return Optimizer(init, update)


def adafactor(cfg: TrainConfig) -> Optimizer:
    """Factored second-moment estimator (Shazeer & Stern). For rank>=2 leaves
    keeps row/col statistics only — the memory saver for the 1T configs."""
    sched = lr_schedule(cfg)
    eps = 1e-30
    clip_thresh = 1.0

    def init(params):
        def zst(p):
            if p.ndim >= 2:
                return {
                    "vr": jnp.zeros(p.shape[:-1], jnp.float32),
                    "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:], jnp.float32),
                }
            return {"v": jnp.zeros(p.shape, jnp.float32)}

        return jax.tree_util.tree_map(zst, params)

    def update(grads, state, params, step):
        lr = sched(step + 1)
        beta2 = 1.0 - (step + 1.0) ** -0.8

        def upd(g, st, p):
            g32 = g.astype(jnp.float32)
            g2 = jnp.square(g32) + eps
            if p.ndim >= 2:
                vr = beta2 * st["vr"] + (1 - beta2) * jnp.mean(g2, axis=-1)
                vc = beta2 * st["vc"] + (1 - beta2) * jnp.mean(g2, axis=-2)
                denom = jnp.maximum(jnp.mean(vr, axis=-1, keepdims=True), eps)
                v = vr[..., None] * vc[..., None, :] / denom[..., None]
                new_st = {"vr": vr, "vc": vc}
            else:
                v = beta2 * st["v"] + (1 - beta2) * g2
                new_st = {"v": v}
            u = g32 / jnp.sqrt(v + eps)
            rms_u = jnp.sqrt(jnp.mean(jnp.square(u)) + eps)
            u = u / jnp.maximum(1.0, rms_u / clip_thresh)
            newp = p.astype(jnp.float32) - lr * u
            return newp.astype(p.dtype), new_st

        is_st = lambda x: isinstance(x, dict) and ("v" in x or "vr" in x)
        flat_p, treedef = jax.tree_util.tree_flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_s = treedef.flatten_up_to(state)
        out = [upd(g, s, p) for g, s, p in zip(flat_g, flat_s, flat_p)]
        new_p = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
        new_s = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
        return new_p, new_s

    return Optimizer(init, update)


def make_optimizer(cfg: TrainConfig) -> Optimizer:
    return {"adamw": adamw, "adafactor": adafactor}[cfg.optimizer](cfg)


def sgd_simple(lr: float) -> Optimizer:
    """Plain SGD (used by tiny property tests)."""

    def init(params):
        return {}

    def update(grads, state, params, step):
        new_p = jax.tree_util.tree_map(
            lambda p, g: (p.astype(jnp.float32) - lr * g.astype(jnp.float32)).astype(p.dtype),
            params, grads,
        )
        return new_p, state

    return Optimizer(init, update)
