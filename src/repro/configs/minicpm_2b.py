"""MiniCPM-2B [arXiv:2404.06395] — llama-like dense arch trained with the WSD
(warmup-stable-decay) schedule; the schedule lives in ``repro.training.optim``.

Published widths, full multi-head attention (36 heads of 64), tied
embeddings, ``rms_norm_eps`` 1e-5. The program has none of MiniCPM's muP
multipliers (``scale_emb`` 12, ``scale_depth`` 1.4, ``dim_model_base`` 256):
it computes the model as if each were 1."""

from repro.common.config import ModelConfig


def make_config() -> ModelConfig:
    return ModelConfig(
        name="minicpm-2b",
        family="dense",
        n_layers=40,
        d_model=2304,
        n_heads=36,
        n_kv_heads=36,
        d_ff=5760,
        vocab_size=122753,
        tie_embeddings=True,
        norm_eps=1e-5,
        citation="arXiv:2404.06395",
    )
