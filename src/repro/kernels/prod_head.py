"""Pallas TPU fused ProD predictor head (the paper's inference-path addition).

One kernel fuses: 2-layer MLP (d -> hidden -> K bins) + softmax + the
quantile-of-predictive-distribution decode (CDF crossing with in-bin linear
interpolation, §2.4 — the median is the q=0.5 special case). Runs on the
served model's last hidden state during prefill — fusing it keeps the paper's
"no additional inference cost" claim honest: one VMEM-resident matmul pair
per request, no HBM round-trips for intermediates. The serving-layer
:class:`~repro.serving.predictor.PredictorService` asks for several quantiles
(median for routing, q0.9 for laxity, the policy quantile for KV reservation)
in the same fused call.

Grid ``(n_batch_blocks, n_d_blocks)``: the first matmul is tiled over ``d``
(512 rows of ``w1`` per step, accumulated into a ``(block_b, hidden)``
float32 VMEM scratch), so VMEM use does not grow with the model width. Whole
``w1`` panels in float32 (the dtype :func:`repro.core.heads.head_init` makes)
overflow the TPU's scoped VMEM beyond about d=4096; tiled, with hidden=512
and K ≤ 128, the double-buffered working set stays under ~3 MB at any d. Every value in the kernel body is 2-D ``(rows, lanes)``, which is what
the TPU lowering (Mosaic) accepts: the CDF is a matmul against an
upper-triangular ``(K, K)`` mask, and the quantile levels are read as
scalars from SMEM in a static loop.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST
_BLOCK_D = 512   # rows of w1 per grid step: 1 MB of float32 at hidden=512


def _dot(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=_HI,
                               preferred_element_type=jnp.float32)


def _prod_head_kernel(phi_ref, w1_ref, b1_ref, w2_ref, b2_ref, left_ref,
                      right_ref, qs_ref, probs_ref, quant_ref, h_acc, *, n_q):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        h_acc[...] = jnp.zeros_like(h_acc)

    h_acc[...] += _dot(phi_ref[...].astype(jnp.float32),
                       w1_ref[...].astype(jnp.float32))

    @pl.when(j == pl.num_programs(1) - 1)
    def _decode():
        h = jnp.maximum(h_acc[...] + b1_ref[...].astype(jnp.float32), 0.0)
        logits = _dot(h, w2_ref[...].astype(jnp.float32)) \
            + b2_ref[...].astype(jnp.float32)                      # (bb, K)
        m = jnp.max(logits, axis=-1, keepdims=True)
        e = jnp.exp(logits - m)
        probs = e / jnp.sum(e, axis=-1, keepdims=True)
        probs_ref[...] = probs

        bb, K = probs.shape
        row = jax.lax.broadcasted_iota(jnp.int32, (K, K), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (K, K), 1)
        cdf = _dot(probs, (row <= col).astype(jnp.float32))          # (bb, K)
        idx = jax.lax.broadcasted_iota(jnp.int32, (bb, K), 1).astype(jnp.float32)
        left = left_ref[...].astype(jnp.float32)                      # (1, K)
        right = right_ref[...].astype(jnp.float32)
        lane_q = jax.lax.broadcasted_iota(jnp.int32, (bb, n_q), 1)
        out = jnp.zeros((bb, n_q), jnp.float32)
        for qi in range(n_q):
            q = qs_ref[qi]
            # first crossing, clamped to the last bin when float32 rounding
            # keeps the CDF below q (q→1) — same rule as ref.prod_head_ref
            k_star = jnp.min(jnp.where(cdf >= q, idx, float(K - 1)),
                             axis=-1, keepdims=True)                  # (bb, 1)
            onehot = (idx == k_star).astype(jnp.float32)              # (bb, K)
            p_k = jnp.sum(probs * onehot, axis=-1, keepdims=True)
            cdf_prev = jnp.sum(cdf * onehot, axis=-1, keepdims=True) - p_k
            t = jnp.clip((q - cdf_prev) / jnp.maximum(p_k, 1e-12), 0.0, 1.0)
            lo = jnp.sum(left * onehot, axis=-1, keepdims=True)
            hi = jnp.sum(right * onehot, axis=-1, keepdims=True)
            out = jnp.where(lane_q == qi, lo + t * (hi - lo), out)
        quant_ref[...] = out


def _block_d(d: int) -> int:
    """Largest multiple of 128 that divides ``d`` and is ≤ ``_BLOCK_D``; the
    whole of ``d`` when it is small or not lane-aligned."""
    if d <= _BLOCK_D or d % 128:
        return d
    bd = _BLOCK_D
    while d % bd:
        bd -= 128
    return bd


def prod_head_pallas(
    phi: jax.Array,       # (B, d)
    w1: jax.Array,        # (d, hidden)
    b1: jax.Array,
    w2: jax.Array,        # (hidden, K)
    b2: jax.Array,
    edges: jax.Array,     # (K+1,)
    *,
    qs: jax.Array = None,  # (Q,) CDF levels; None -> median only
    block_b: int = 128,
    interpret: bool = False,
):
    """Fused MLP + softmax + interpolated CDF-crossing decode.

    Returns ``(probs (B, K) fp32, median (B,) fp32)`` when ``qs`` is None
    (the original single-quantile shape), else ``(probs, quants (B, Q))``
    with one column per requested CDF level."""
    single = qs is None
    qs = jnp.array([0.5], jnp.float32) if single else jnp.asarray(qs, jnp.float32)
    Q = qs.shape[0]
    B, d = phi.shape
    hidden = w1.shape[1]
    K = w2.shape[1]
    block_b = min(block_b, B)
    pad = (-B) % block_b
    if pad:
        phi = jnp.pad(phi, ((0, pad), (0, 0)))
    nb = (B + pad) // block_b
    bd = _block_d(d)
    nd = d // bd
    edges = edges.astype(jnp.float32)

    probs, quants = pl.pallas_call(
        functools.partial(_prod_head_kernel, n_q=Q),
        grid=(nb, nd),
        in_specs=[
            pl.BlockSpec((block_b, bd), lambda i, j: (i, j)),
            pl.BlockSpec((bd, hidden), lambda i, j: (j, 0)),
            pl.BlockSpec((1, hidden), lambda i, j: (0, 0)),
            pl.BlockSpec((hidden, K), lambda i, j: (0, 0)),
            pl.BlockSpec((1, K), lambda i, j: (0, 0)),
            pl.BlockSpec((1, K), lambda i, j: (0, 0)),
            pl.BlockSpec((1, K), lambda i, j: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((block_b, K), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b, Q), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B + pad, K), jnp.float32),
            jax.ShapeDtypeStruct((B + pad, Q), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_b, hidden), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(phi, w1, b1.reshape(1, hidden), w2, b2.reshape(1, K),
      edges[None, :K], edges[None, 1:], qs)
    if single:
        return probs[:B], quants[:B, 0]
    return probs[:B], quants[:B]
