"""Random weights of a dense decoder, drawn on the device from the seed.

The benchmark makes the weights itself, so that its plain reference reads
nothing the program made. They are drawn in one jitted call, in bfloat16 as
they are served, in a layout that is the published one with the layers
stacked on a leading axis:

  embed (V, d), head (d, V) unless the embeddings are tied, final_norm (d,),
  ln1, ln2 (L, d), wq (L, d, H, hd), wk, wv (L, d, KV, hd), wo (L, H, hd, d),
  w_gate, w_up (L, d, F), w_down (L, F, d)

Norm gains are float32 values 1 + delta with delta exactly a bfloat16, so that
a program that stores ``gain - 1`` holds them without rounding.

The ProD head (``draw_head``) is float32, as ``repro.core.heads`` keeps it.
"""

from __future__ import annotations

import math

import numpy as np


def key_from_seed(seed: int):
    """A PRNG key that keeps every bit of a seed of up to 64 bits (a bare
    ``PRNGKey`` drops the bits above 32)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def dims(cfg: dict) -> dict:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"d": d, "H": H, "KV": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or d // H, "F": cfg["intermediate_size"],
            "V": cfg["vocab_size"], "L": cfg["num_hidden_layers"],
            "tied": bool(cfg["tie_word_embeddings"])}


def layout(cfg: dict) -> dict:
    """name -> (shape, standard deviation); a std of None marks a norm gain."""
    m = dims(cfg)
    d, H, KV, hd, F, V, L = (m[k] for k in ("d", "H", "KV", "hd", "F", "V", "L"))
    out = {"embed": ((V, d), 1 / math.sqrt(d)), "final_norm": ((d,), None),
           "ln1": ((L, d), None), "ln2": ((L, d), None),
           "wq": ((L, d, H, hd), 1 / math.sqrt(d)),
           "wk": ((L, d, KV, hd), 1 / math.sqrt(d)),
           "wv": ((L, d, KV, hd), 1 / math.sqrt(d)),
           "wo": ((L, H, hd, d), 1 / math.sqrt(H * hd)),
           "w_gate": ((L, d, F), 1 / math.sqrt(d)),
           "w_up": ((L, d, F), 1 / math.sqrt(d)),
           "w_down": ((L, F, d), 1 / math.sqrt(F))}
    if not m["tied"]:
        out["head"] = ((d, V), 1 / math.sqrt(d))
    return out


NORM_SPREAD = 0.1


def draw(cfg: dict, seed: int) -> dict:
    """Every weight of ``cfg``, on the default device, from ``seed``."""
    import jax
    import jax.numpy as jnp

    spec = layout(cfg)
    names = sorted(spec)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(names))
        out = {}
        for k, name in zip(keys, names):
            shape, std = spec[name]
            z = jax.random.normal(k, shape, jnp.float32)
            if std is None:
                delta = (NORM_SPREAD * z).astype(jnp.bfloat16).astype(jnp.float32)
                out[name] = 1.0 + delta
            else:
                out[name] = (std * z).astype(jnp.bfloat16)
        return out

    return make(jax.random.fold_in(key_from_seed(seed), 0))


def draw_head(cfg: dict, seed: int) -> dict:
    """A ProD-D head (d -> hidden -> n_bins) from ``seed``, float32, with
    biases drawn too so that every parameter takes part."""
    import jax
    import jax.numpy as jnp

    d, ph = cfg["hidden_size"], cfg["prod_head"]
    hid, K = ph["hidden"], ph["n_bins"]
    k1, k2, k3, k4 = jax.random.split(jax.random.fold_in(key_from_seed(seed), 1), 4)
    return {"w1": jax.random.normal(k1, (d, hid)) / math.sqrt(d),
            "b1": 0.1 * jax.random.normal(k2, (hid,)),
            "w2": 2.0 * jax.random.normal(k3, (hid, K)) / math.sqrt(hid),
            "b2": 0.5 * jax.random.normal(k4, (K,))}


def head_edges(cfg: dict) -> np.ndarray:
    """Linear bin edges of the ProD head, (n_bins + 1,) float32."""
    ph = cfg["prod_head"]
    return np.linspace(0.0, ph["bin_max"], ph["n_bins"] + 1).astype(np.float32)
