"""Configuration system.

Every architecture in ``repro.configs`` produces a :class:`ModelConfig`. The
config is a frozen dataclass so it can be closed over by jitted functions and
hashed as a static argument.

Families
--------
``dense``   decoder-only transformer (GQA/MQA, optional sliding/local-global mix)
``moe``     dense skeleton with MoE FFN every layer (top-k router, expert parallel)
``ssm``     attention-free Mamba2 / SSD stack
``hybrid``  Mamba2 backbone with a shared attention block applied periodically
``encdec``  Whisper-style encoder-decoder (audio frontend stubbed)
``vlm``     decoder LM consuming interleaved text/patch embeddings with M-RoPE
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field


@dataclass(frozen=True)
class ModelConfig:
    """Architecture hyper-parameters (the exact assigned values live in repro.configs)."""

    name: str
    family: str                      # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 -> d_model // n_heads

    # --- attention pattern -------------------------------------------------
    attn_window: int = 0             # 0 = full attention; >0 = sliding window size
    local_global_ratio: int = 0      # gemma3: N local layers per 1 global layer (0=uniform)
    rope_theta: float = 10000.0
    rope_theta_local: float = 10000.0  # gemma3 uses a different base for local layers
    use_mrope: bool = False          # qwen2-vl multimodal RoPE (3 position streams)
    qk_norm: bool = False

    # --- MoE ---------------------------------------------------------------
    n_experts: int = 0
    n_experts_per_token: int = 0
    n_shared_experts: int = 0        # kimi-k2 style always-on shared expert(s)
    moe_d_ff: int = 0                # per-expert hidden size (0 -> d_ff)
    router_aux_coef: float = 0.01

    # --- SSM (mamba2 / zamba2) ----------------------------------------------
    ssm_state: int = 0               # d_state N
    ssm_heads: int = 0               # number of SSD heads (0 -> derived)
    ssm_head_dim: int = 64           # P
    ssm_chunk: int = 256             # SSD chunk length
    ssm_conv_width: int = 4
    attn_every: int = 0              # hybrid: apply shared attn block every k ssm layers

    # --- enc-dec -----------------------------------------------------------
    n_encoder_layers: int = 0
    encoder_seq: int = 1500          # whisper mel-frame positions after conv stub

    # --- misc --------------------------------------------------------------
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    act: str = "silu"                # silu (gated) | gelu (whisper-style plain MLP)
    dtype: str = "bfloat16"
    citation: str = ""

    # --- ProD head (paper core, attached to every arch) ---------------------
    predictor_bins: int = 64
    predictor_hidden: int = 512
    predictor_bin_max: float = 8192.0

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if self.family == "moe" and self.moe_d_ff == 0:
            object.__setattr__(self, "moe_d_ff", self.d_ff)
        assert self.n_heads % max(self.n_kv_heads, 1) == 0 or self.family in ("ssm",), (
            f"{self.name}: n_heads={self.n_heads} not divisible by kv={self.n_kv_heads}"
        )

    # -- derived ------------------------------------------------------------
    @property
    def q_per_kv(self) -> int:
        return self.n_heads // max(self.n_kv_heads, 1)

    @property
    def ssm_n_heads(self) -> int:
        if self.ssm_heads:
            return self.ssm_heads
        return (2 * self.d_model) // self.ssm_head_dim  # mamba2 default d_inner=2*d

    def param_count(self) -> int:
        """Analytic parameter count (used for 6ND model-FLOPs in the roofline)."""
        d, L, V = self.d_model, self.n_layers, self.vocab_size
        hd = self.head_dim
        emb = V * d * (1 if self.tie_embeddings else 2)
        attn = d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd + self.n_heads * hd * d

        def ffn_gated(ff):
            return 3 * d * ff

        def ffn_plain(ff):
            return 2 * d * ff

        ffn_fn = ffn_gated if self.act == "silu" else ffn_plain
        per_layer = 0
        if self.family in ("dense", "vlm"):
            per_layer = attn + ffn_fn(self.d_ff)
            total = L * per_layer
        elif self.family == "moe":
            shared = self.n_shared_experts * ffn_fn(self.moe_d_ff)
            per_layer = attn + self.n_experts * ffn_fn(self.moe_d_ff) + shared + d * self.n_experts
            total = L * per_layer
        elif self.family == "ssm":
            total = L * self._ssm_layer_params()
        elif self.family == "hybrid":
            n_attn = max(1, L // max(self.attn_every, 1)) if self.attn_every else 1
            total = L * self._ssm_layer_params() + (attn + ffn_fn(self.d_ff))  # shared block counted once
            del n_attn
        elif self.family == "encdec":
            enc = self.n_encoder_layers * (attn + ffn_fn(self.d_ff))
            dec = L * (2 * attn + ffn_fn(self.d_ff))  # self + cross attention
            total = enc + dec
        else:
            raise ValueError(self.family)
        return int(total + emb)

    def active_param_count(self) -> int:
        """Activated params per token (MoE uses top-k + shared experts only)."""
        if self.family != "moe":
            return self.param_count()
        d, L = self.d_model, self.n_layers
        hd = self.head_dim
        attn = d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd + self.n_heads * hd * d
        ffn = 3 * d * self.moe_d_ff
        active_layer = attn + (self.n_experts_per_token + self.n_shared_experts) * ffn + d * self.n_experts
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return int(L * active_layer + emb)

    def _ssm_layer_params(self) -> int:
        d = self.d_model
        d_inner = self.ssm_n_heads * self.ssm_head_dim
        n = self.ssm_state
        # in_proj (z, x, B, C, dt), conv, A, D, norm, out_proj — mamba2 layout
        return (
            d * (2 * d_inner + 2 * self.ssm_state_groups() * n + self.ssm_n_heads)
            + d_inner * self.ssm_conv_width
            + 2 * self.ssm_n_heads
            + d_inner * d
        )

    def ssm_state_groups(self) -> int:
        return 1

    def with_overrides(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def reduced(self) -> "ModelConfig":
        """Smoke-test variant of the same family: 2 layers, d_model<=512, <=4 experts."""
        d = min(self.d_model, 256)
        heads = min(self.n_heads, 4)
        kv = min(self.n_kv_heads, heads)
        while heads % kv:
            kv -= 1
        kw = dict(
            name=self.name + "-smoke",
            n_layers=2,
            d_model=d,
            n_heads=heads,
            n_kv_heads=kv,
            head_dim=d // heads,
            d_ff=min(self.d_ff, 512) or self.d_ff,
            vocab_size=min(self.vocab_size, 512),
            encoder_seq=min(self.encoder_seq, 32),
        )
        if self.family == "moe":
            kw.update(n_experts=4, n_experts_per_token=2, moe_d_ff=128,
                      n_shared_experts=min(self.n_shared_experts, 1))
        if self.family in ("ssm", "hybrid"):
            kw.update(ssm_state=min(self.ssm_state, 16), ssm_heads=4, ssm_head_dim=32,
                      ssm_chunk=16)
        if self.family == "hybrid":
            kw.update(attn_every=1)
        if self.n_encoder_layers:
            kw.update(n_encoder_layers=2)
        if self.attn_window:
            kw.update(attn_window=min(self.attn_window, 16))
        if self.local_global_ratio:
            kw.update(local_global_ratio=min(self.local_global_ratio, 1))
        return self.with_overrides(**kw)


@dataclass(frozen=True)
class TrainConfig:
    optimizer: str = "adamw"          # adamw | adafactor
    lr: float = 3e-4
    schedule: str = "cosine"          # cosine | wsd | constant
    warmup_steps: int = 100
    decay_steps: int = 10_000
    stable_steps: int = 0             # WSD plateau
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    grad_clip: float = 1.0
    microbatch: int = 0               # 0 = no gradient accumulation
    remat: str = "full"               # none | full | dots
    seed: int = 0


@dataclass(frozen=True)
class PredictorConfig:
    """ProD head + supervision protocol (paper §2.4 / A.2)."""

    n_bins: int = 64
    hidden: int = 512
    bin_max: float = 8192.0
    bin_spacing: str = "linear"       # linear | log (log is a beyond-paper option)
    r_samples: int = 16               # repeated-sampling budget r
    target: str = "median"            # median (ProD-M) | dist (ProD-D) | single
    decode: str = "median"            # median | argmax | mean
    lr: float = 1e-3
    epochs: int = 30
    batch_size: int = 256
    weight_decay: float = 0.0
    seed: int = 0
