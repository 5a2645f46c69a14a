"""Entry adapter: the static-batch engine ``RealEngine.generate`` and the
ProD-D head after it, as ``PredictorService`` calls it.

A round is one batch: the B prompts of a closed loop's clients go to
``RealEngine(model, params, max_new=...).generate(prompts, lengths, key)``,
which prefills them, decodes ``max_new`` steps and returns every token with
the last-prompt-token hidden states φ; the jitted
``LengthPredictor.quantiles(φ, qs, impl="pallas")`` then gives each request
its length quantiles. A reply is a request's tokens with its quantiles.

The program is handed the benchmark's own weights: the adapter only arranges
them into the program's parameter tree (norm gains as ``gain - 1``, which the
program's RMSNorm adds 1 back to). A greedy round sets the engine's sampling
temperature to ``GREEDY_TEMPERATURE``, at which the engine's categorical draw
is the largest logit unless two lie within about 1e-4 of each other.
"""

from __future__ import annotations

import math
import time

import numpy as np

GREEDY_TEMPERATURE = 1e-5
SCOPES = ("fusedkernel_decode_attention", "fusedkernel_flash_attention")


def model_config(cfg: dict):
    from repro.common.config import ModelConfig

    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    L = cfg["num_hidden_layers"]
    # the program has no muP multipliers and no q/k/v biases
    multipliers = {"scale_emb": cfg.get("scale_emb", 1),
                   "scale_depth": cfg.get("scale_depth", math.sqrt(L)) / math.sqrt(L),
                   "dim_model_base": d / cfg.get("dim_model_base", d)}
    for key, value in multipliers.items():
        if not math.isclose(value, 1.0, rel_tol=1e-6):
            raise ValueError(f"the program has no {key}; the file makes it {value}")
    if cfg.get("attention_bias"):
        raise ValueError("the program has no q/k/v biases; the file asks for them")
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=L,
        d_model=d, n_heads=H, n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg.get("head_dim") or d // H, rope_theta=cfg["rope_theta"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        norm_eps=cfg["rms_norm_eps"], act="silu", dtype="bfloat16")


def program_params(W: dict):
    """The program's parameter tree over the benchmark's arrays."""
    import jax.numpy as jnp

    gain = lambda g: (g - 1.0).astype(jnp.bfloat16)  # exact: see weights.py
    layer = {"ln1": gain(W["ln1"]), "ln2": gain(W["ln2"]),
             "attn": {k: W[k] for k in ("wq", "wk", "wv", "wo")},
             "mlp": {k: W[k] for k in ("w_gate", "w_up", "w_down")}}
    p = {"embed": W["embed"], "final_norm": gain(W["final_norm"]),
         "segments": [{"layer_0": layer}]}
    if "head" in W:
        p["head"] = W["head"]
    return p


class Entry:
    def __init__(self, cfg: dict, mix: dict, W: dict, head_w: dict, edges,
                 impl: str = "pallas"):
        import jax
        import jax.numpy as jnp

        from repro.common.config import PredictorConfig
        from repro.core.predictor import LengthPredictor
        from repro.models.model_zoo import build_model
        from repro.serving.engine import RealEngine

        self.model = build_model(model_config(cfg))
        self.params = program_params(W)
        want = jax.tree_util.tree_map(lambda s: (s.shape, str(s.dtype)),
                                      self.model.param_shapes())
        have = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), self.params)
        if want != have:
            raise ValueError("the benchmark's weights do not fit the program's tree")
        self.temperature = float(mix["temperature"])
        self.eng = RealEngine(self.model, self.params, temperature=self.temperature,
                              max_new=int(mix["max_new"]),
                              eos_id=int(cfg["eos_token_id"]))
        self.qs = tuple(float(q) for q in mix["quantiles"])
        ph = cfg["prod_head"]
        pcfg = PredictorConfig(n_bins=ph["n_bins"], hidden=ph["hidden"],
                               bin_max=ph["bin_max"])
        self.head_w = head_w
        self.edges = jnp.asarray(edges, jnp.float32)
        qs = self.qs

        def prod_head_quantiles(params, phi, edges):
            return LengthPredictor(params, edges, pcfg).quantiles(phi, qs, impl=impl)

        self.head_fn = jax.jit(prod_head_quantiles)
        self.timings = []      # per round: prefill_s, decode_s, decode_steps, head_s
        self._shapes = None

    def serve(self, prompts, plens, key, greedy: bool):
        """One round. Returns (tokens (B, max_new), lengths (B,), φ (B, d) as
        the head got it, quantiles (B, Q)) on the host."""
        import jax
        import jax.numpy as jnp

        self.eng.temp = GREEDY_TEMPERATURE if greedy else self.temperature
        with jax.profiler.TraceAnnotation("chipbench.generate"):
            out = self.eng.generate(prompts, plens, key)
        with jax.profiler.TraceAnnotation("chipbench.head"):
            t = time.perf_counter()
            phi = jnp.asarray(out["phi"], jnp.float32)
            _, q = self.head_fn(self.head_w, phi, self.edges)
            q = np.asarray(jax.block_until_ready(q))
            head_s = time.perf_counter() - t
        self.timings.append(dict(self.eng.timings[-1], head_s=head_s))
        self._shapes = prompts.shape
        return out["tokens"], out["lengths"], np.asarray(phi), q

    def compiled_texts(self):
        """Compiled text of the prefill and decode programs at the shapes
        served, for reading named scopes out of a trace."""
        import jax
        import jax.numpy as jnp

        B, Sp = self._shapes
        S = Sp + self.eng.max_new
        sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt)
        batch = {"tokens": sds((B, Sp), jnp.int32), "attn_valid": sds((B, Sp), jnp.bool_)}
        pre = self.eng._jit_prefill.lower(self.params, batch, sds((B,), jnp.int32))
        cache = jax.tree_util.tree_map(lambda s: sds(s.shape, s.dtype),
                                       self.model.cache_shapes(B, S))
        dbatch = {k: sds((B,), jnp.int32) for k in ("tokens", "pos", "lengths")}
        dec = self.eng._jit_decode.lower(self.params, dbatch, cache)
        return [pre.compile().as_text(), dec.compile().as_text()]

    def close(self):
        """Drop the program's state (engine, jitted programs); the weights
        are the benchmark's and stay."""
        self.eng = self.head_fn = self.params = None
