"""Parameter-spec machinery + elementary layers (norms, MLP, embeddings).

A model is described once as a *spec tree* — nested dicts with
:class:`ParamSpec` leaves (shape + logical sharding axes + initializer).
From the single spec we derive:

* ``init_tree``   — materialized parameters (smoke tests, tiny-LM runs),
* ``shape_tree``  — ShapeDtypeStructs (multi-pod dry-run: zero allocation),
* ``axes_tree``   — logical-axis tuples (resolved to NamedShardings at launch).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"        # normal | zeros | ones | embed
    scale: Optional[float] = None  # None -> 1/sqrt(fan_in) for "normal"

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def init_tree(key: jax.Array, spec_tree: Any, dtype) -> Any:
    leaves, treedef = jax.tree_util.tree_flatten(spec_tree, is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    dtype = jnp.dtype(dtype)
    return jax.tree_util.tree_unflatten(
        treedef, [_init_leaf(k, s, dtype) for k, s in zip(keys, leaves)]
    )


@functools.partial(jax.jit, static_argnums=(1, 2))
def _init_leaf(k: jax.Array, s: ParamSpec, dtype) -> jax.Array:
    """One leaf, drawn and cast inside one program: the float32 draw of a
    stacked (layers, d, d_ff) leaf is fused into the cast and never lands in
    device memory whole (at full width it alone would be several GB)."""
    if s.init == "zeros":
        return jnp.zeros(s.shape, dtype)
    if s.init == "ones":
        return jnp.ones(s.shape, dtype)
    if s.init == "embed":
        return (jax.random.normal(k, s.shape) * (s.scale or 0.02)).astype(dtype)
    fan_in = s.shape[-2] if len(s.shape) >= 2 else s.shape[-1]
    scale = s.scale if s.scale is not None else 1.0 / np.sqrt(max(fan_in, 1))
    return (jax.random.normal(k, s.shape) * scale).astype(dtype)


def shape_tree(spec_tree: Any, dtype) -> Any:
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(dtype)),
        spec_tree,
        is_leaf=_is_spec,
    )


def axes_tree(spec_tree: Any) -> Any:
    return jax.tree_util.tree_map(lambda s: s.axes, spec_tree, is_leaf=_is_spec)


def stack_specs(spec_tree: Any, n: int, axis_name: str = "layers") -> Any:
    """Prepend a stacked (scan) dimension to every spec in the tree."""

    def one(s: ParamSpec) -> ParamSpec:
        return ParamSpec((n,) + s.shape, (axis_name,) + s.axes, s.init, s.scale)

    return jax.tree_util.tree_map(one, spec_tree, is_leaf=_is_spec)


# ---------------------------------------------------------------------------
# elementary ops
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(dt)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32) + bias.astype(
        jnp.float32
    )
    return out.astype(dt)


def _act(x: jax.Array, kind: str) -> jax.Array:
    if kind in ("silu", "gelu_gated"):
        return jax.nn.silu(x) if kind == "silu" else jax.nn.gelu(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    raise ValueError(kind)


def mlp_spec(d_model: int, d_ff: int, act: str) -> Dict[str, ParamSpec]:
    if act in ("silu", "gelu_gated"):
        return {
            "w_gate": ParamSpec((d_model, d_ff), ("embed", "ffn")),
            "w_up": ParamSpec((d_model, d_ff), ("embed", "ffn")),
            "w_down": ParamSpec((d_ff, d_model), ("ffn", "embed")),
        }
    return {
        "w_up": ParamSpec((d_model, d_ff), ("embed", "ffn")),
        "w_down": ParamSpec((d_ff, d_model), ("ffn", "embed")),
    }


def mlp_apply(p: Dict[str, jax.Array], x: jax.Array, act: str) -> jax.Array:
    if "w_gate" in p:
        h = _act(x @ p["w_gate"], act) * (x @ p["w_up"])
    else:
        h = _act(x @ p["w_up"], act)
    return h @ p["w_down"]


def embed_spec(vocab: int, d_model: int) -> ParamSpec:
    return ParamSpec((vocab, d_model), ("vocab", "embed"), init="embed")


def unembed(x: jax.Array, w_embed: jax.Array, w_head: Optional[jax.Array]) -> jax.Array:
    """Project hidden states to float32 vocab logits (for loss stability).

    The (d, V) matrix is read in its own dtype and accumulated in float32:
    casting it first would write a float32 copy of the whole vocabulary
    projection on every call."""
    w = w_embed.T if w_head is None else w_head
    dt = jnp.promote_types(x.dtype, w.dtype)
    return jnp.matmul(x.astype(dt), w.astype(dt),
                      preferred_element_type=jnp.float32)


def sinusoid_positions(n_pos: int, d_model: int) -> np.ndarray:
    """Whisper-style fixed sinusoidal embeddings (numpy: baked as constant)."""
    half = d_model // 2
    freqs = np.exp(-np.log(10000.0) * np.arange(half) / max(half - 1, 1))
    ang = np.arange(n_pos)[:, None] * freqs[None, :]
    return np.concatenate([np.sin(ang), np.cos(ang)], axis=1).astype(np.float32)
