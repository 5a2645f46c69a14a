"""Public jit'd wrappers for the Pallas kernels with impl dispatch.

``impl``:
* ``"auto"``      — Pallas on TPU backends, XLA/jnp oracle elsewhere (CPU CI).
* ``"pallas"``    — compiled Pallas (TPU).
* ``"interpret"`` — Pallas in interpret mode (kernel body executed in Python
                    on CPU; used by the correctness test sweeps).
* ``"xla"``       — the pure-jnp reference path.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.prod_head import prod_head_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if _on_tpu() else "xla"
    return impl


def flash_attention(q, k, v, lengths=None, *, causal=True, window=0,
                    block_q=None, block_kv=None, impl="auto"):
    impl = resolve(impl)
    if impl == "xla":
        return ref.flash_attention_ref(q, k, v, lengths, causal=causal, window=window)
    return flash_attention_pallas(
        q, k, v, lengths, causal=causal, window=window, block_q=block_q,
        block_kv=block_kv, interpret=(impl == "interpret"),
    )


def decode_attention(q, k, v, lengths, *, block_kv=256, impl="auto"):
    impl = resolve(impl)
    if impl == "xla":
        return ref.decode_attention_ref(q, k, v, lengths)
    return decode_attention_pallas(
        q, k, v, lengths, block_kv=block_kv, interpret=(impl == "interpret")
    )


def ssd_scan(x, dt, a, Bm, Cm, *, chunk=128, impl="auto"):
    impl = resolve(impl)
    if impl == "xla":
        return ref.ssd_scan_ref(x, dt, a, Bm, Cm)
    S = x.shape[1]
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        # padded steps must not decay the carried state: a=0 and dt=0
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    y, h = ssd_scan_pallas(x, dt, a, Bm, Cm, chunk=chunk,
                           interpret=(impl == "interpret"))
    return y[:, :S], h


def prod_head(phi, w1, b1, w2, b2, edges, *, qs=None, block_b=128, impl="auto"):
    """Fused head. ``qs=None`` returns (probs, median); ``qs`` an array of
    CDF levels returns (probs, quants (B, Q)) — all levels in one call."""
    impl = resolve(impl)
    if impl == "xla":
        return ref.prod_head_ref(phi, w1, b1, w2, b2, edges, qs=qs)
    return prod_head_pallas(phi, w1, b1, w2, b2, edges, qs=qs, block_b=block_b,
                            interpret=(impl == "interpret"))
