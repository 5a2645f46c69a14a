"""Pallas TPU flash attention: the served prefill's attention.

Causal (optionally windowed) attention over right-padded rows: with
``lengths``, row ``b`` holds ``lengths[b]`` real tokens, keys past them are
masked and queries past them return zeros. q, k and v keep their own layout,
heads and head width merged in the minor dimension, so nothing is transposed
around the kernel: a *lane group* of ``W = lcm(hd, 128)`` lanes holds
``P = W // hd`` heads (one head at hd 128, two at hd 64, whose scores are
taken against a query zeroed outside each head's lanes).

Grid ``(B, lane groups, q blocks, kv chunks)``, kv chunks innermost. A step
holds one q block of one lane group and one chunk of keys and values, and
loops inside over the kv blocks of the chunk that the causal mask and the
row's length leave (:func:`live_kv_blocks`): blocks that every query of the
q block sees whole run without a mask, the rest are masked from iota, and
the others are not computed. A chunk with no live block is clamped in its
index map to a live one, so the pipeline sees the block it already holds and
issues no DMA; a q block wholly past the prompt writes zeros and keeps the
query, key and value blocks the last live q block held (:func:`kv_chunk`).
At the served lengths one chunk holds the whole row, so a row's keys and
values are read once per kv head.

Both products take q, k and v in their own dtype (bf16) into the MXU with
float32 accumulation; the softmax statistics and the accumulator are
float32, and p is cast to v's dtype before the PV product. The running max
and sum of each query row are kept replicated across 128 lanes, as the
scores are laid out, so that no step has to broadcast them across lanes
(on a v5e that broadcast took 39% of the kernel's time at 512 blocks).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
SCOPE = "fusedkernel_flash_attention"
LANES = 128
# keys and values of one chunk, double-buffered, stay under this many bytes
KV_CHUNK_BYTES = 8 * 1024 * 1024


def prefill_blocks(seq_len: int):
    """(block_q, block_kv) for a sequence padded to ``seq_len``: square
    blocks of 512, or one block for a shorter sequence. On a TPU v5e, 512
    was the fastest of the sizes 256 to 1024 at head widths 128 and 64:
    larger blocks compute more of the diagonal's masked half, smaller ones
    take more steps."""
    b = min(512, _round_up(seq_len, 16))
    return b, b


def live_kv_blocks(q_block, length, *, block_q, block_kv, causal=True,
                   window=0, xp=jnp):
    """(lo, mid, hi): the kv blocks q block ``q_block`` of a row of
    ``length`` real tokens visits are ``[lo, hi)``; of them ``[lo, mid)`` are
    seen whole by every query of the block and need no mask. A q block past
    the row (``q_block * block_q >= length``) visits none, since queries past
    the prompt are zeroed; the caller checks that. ``xp`` is ``jnp`` inside
    the kernel and ``np`` on the host, so both count the same blocks."""
    q0 = q_block * block_q
    last_key = xp.minimum(q0 + block_q - 1, length - 1) if causal else length - 1
    hi = last_key // block_kv + 1
    lo = xp.maximum((q0 - window + 1) // block_kv, 0) if window else 0 * hi
    if window:
        mid = lo
    else:
        full = length // block_kv
        if causal:
            full = xp.minimum(full, (q0 + 1) // block_kv)
        mid = xp.clip(full, lo, xp.maximum(hi, lo))
    return lo, mid, hi


def kv_chunk(q_block, kc, length, *, block_q, block_kv, chunk_blocks,
             causal=True, window=0, xp=jnp):
    """The chunk of ``chunk_blocks`` kv blocks that grid step (``q_block``,
    ``kc``) of a row of ``length`` real tokens holds: ``kc`` clamped to the
    chunks with a live block. A q block past the row holds the last chunk of
    the last live q block, so that the pipeline fetches nothing for it."""
    q_last = xp.maximum(length - 1, 0) // block_q
    lo, _, hi = live_kv_blocks(xp.minimum(q_block, q_last), length, block_q=block_q,
                               block_kv=block_kv, causal=causal, window=window, xp=xp)
    first = lo // chunk_blocks
    last = xp.maximum(hi - 1, lo) // chunk_blocks
    return xp.where(q_block > q_last, last, xp.clip(kc, first, last))


def live_block_count(lengths, seq_len: int, block_q: int, block_kv: int,
                     causal: bool = True, window: int = 0):
    """(live, all) (q block, kv block) pairs of one head over rows of
    ``lengths`` real tokens padded to ``seq_len``: the pairs the kernel
    computes, and its full square."""
    bq, bkv = block_q, block_kv
    nq, nkv = -(-seq_len // bq), -(-seq_len // bkv)
    lengths = np.asarray(lengths, np.int64)[:, None]
    qb = np.arange(nq)[None, :]
    lo, _, hi = live_kv_blocks(qb, lengths, block_q=bq, block_kv=bkv,
                               causal=causal, window=window, xp=np)
    live = np.where(qb * bq < lengths, np.maximum(hi - lo, 0), 0)
    return int(live.sum()), int(lengths.shape[0] * nq * nkv)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _lanes(x, n):
    """Lane-replicated (rows, 128) statistics as (rows, n)."""
    return jnp.tile(x, (1, -(-n // LANES)))[:, :n]


def _flash_kernel(
    len_ref,                          # SMEM (B,) int32, scalar-prefetched
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale: float, causal: bool, window: int,
    block_q: int, block_kv: int, chunk: int, heads: int, head_dim: int,
):
    b, qi, kc = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    length = len_ref[b]
    lo, mid, hi = live_kv_blocks(qi, length, block_q=block_q, block_kv=block_kv,
                                 causal=causal, window=window)
    per = chunk // block_kv
    c0 = kc * per
    lo, mid, hi = (jnp.clip(x, c0, c0 + per) for x in (lo, mid, hi))

    @pl.when(kc == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    W = q_ref.shape[-1]
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1) // head_dim

    def visit(masked, j, carry):
        start = pl.multiple_of((j - c0) * block_kv, block_kv)
        k = k_ref[0, pl.ds(start, block_kv), :]             # (bkv, W)
        v = v_ref[0, pl.ds(start, block_kv), :]
        q = q_ref[0]                                        # (bq, W)
        if masked:
            shape = (block_q, block_kv)
            qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            kpos = j * block_kv + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            mask = kpos < length
            if causal:
                mask &= kpos <= qpos
            if window:
                mask &= (qpos - kpos) < window
        for p in range(heads):
            qp = q if heads == 1 else jnp.where(lane_head == p, q, 0)
            s = jax.lax.dot_general(
                qp, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale                                       # (bq, bkv)
            if masked:
                s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[p]                               # (bq, 128)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            e = jnp.exp(s - _lanes(m_new, block_kv))
            alpha = jnp.exp(m_prev - m_new)
            l_ref[p] = l_ref[p] * alpha + jnp.sum(e, axis=-1, keepdims=True)
            acc_ref[p] = acc_ref[p] * _lanes(alpha, W) + jax.lax.dot_general(
                e.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[p] = m_new
        return carry

    @pl.when(qi * block_q < length)
    def _run():
        jax.lax.fori_loop(lo, mid, functools.partial(visit, False), 0)
        jax.lax.fori_loop(mid, hi, functools.partial(visit, True), 0)

    @pl.when(kc == pl.num_programs(3) - 1)
    def _fini():
        out = 0.0
        for p in range(heads):
            o = acc_ref[p] / _lanes(jnp.maximum(l_ref[p], 1e-30), W)
            out = o if heads == 1 else out + jnp.where(lane_head == p, o, 0.0)
        rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
        out = jnp.where(rows < length, out, 0.0)
        o_ref[0] = out.astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,                     # (B, Sq, H, hd)
    k: jax.Array,                     # (B, Skv, KV, hd)
    v: jax.Array,                     # (B, Skv, KV, hd)
    lengths=None,                     # (B,) int32 real tokens per row; Skv if None
    *,
    causal: bool = True,
    window: int = 0,
    block_q=None,
    block_kv=None,
    interpret: bool = False,
) -> jax.Array:
    """Returns (B, Sq, H, hd): keys at or past a row's length are masked
    and its queries there give zeros."""
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    scale = 1.0 / math.sqrt(hd)
    with jax.named_scope(SCOPE):
        if lengths is None:
            lengths = jnp.full((B,), Skv, jnp.int32)
        lengths = jnp.asarray(lengths, jnp.int32)
        W = hd * LANES // math.gcd(hd, LANES)
        P = W // hd                              # heads per lane group
        if P > 1 and KV != H:                    # a lane group needs its own K/V heads
            k, v = (jnp.repeat(t, H // KV, axis=2) for t in (k, v))
            KV = H
        groups = pl.cdiv(H, P)                   # lane groups of query heads
        Hp = groups * P
        if Hp != H:
            q, k, v = (jnp.pad(t, ((0, 0), (0, 0), (0, Hp - H), (0, 0))) for t in (q, k, v))
            KV = Hp
        G = Hp // KV                             # query heads per K/V head (P == 1)

        dq, dkv = prefill_blocks(max(Sq, Skv))
        bq = block_q or dq
        bkv = block_kv or dkv
        per = max(1, KV_CHUNK_BYTES // (4 * bkv * W * k.dtype.itemsize))
        chunk = bkv * min(per, pl.cdiv(Skv, bkv))
        nq, nkc = pl.cdiv(Sq, bq), pl.cdiv(Skv, chunk)
        Sqp, Skvp = nq * bq, nkc * chunk

        def lanes_merged(t, S):      # (B, S', heads, hd) -> (B, S, heads·hd)
            t = jnp.pad(t, ((0, 0), (0, S - t.shape[1]), (0, 0), (0, 0)))
            return t.reshape(B, S, -1)

        # on TPU, XLA lays q, k and v out for the projections and the rope and
        # copies them into this merged layout; the barrier keeps those copies
        # under this scope, where the rope's fusion would otherwise name them
        q, k, v = jax.lax.optimization_barrier((q, k, v))
        qf, kf, vf = lanes_merged(q, Sqp), lanes_merged(k, Skvp), lanes_merged(v, Skvp)

        def q_index(b, g, qi, kc, lens):   # past the prompt: the block held
            return (b, jnp.minimum(qi, jnp.maximum(lens[b] - 1, 0) // bq), g)

        def kv_index(b, g, qi, kc, lens):
            c = kv_chunk(qi, kc, lens[b], block_q=bq, block_kv=bkv,
                         chunk_blocks=chunk // bkv, causal=causal, window=window)
            return (b, c, g if P > 1 else g // G)

        kernel = functools.partial(
            _flash_kernel, scale=scale, causal=causal, window=window,
            block_q=bq, block_kv=bkv, chunk=chunk,
            heads=P, head_dim=hd)
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(B, groups, nq, nkc),
                in_specs=[pl.BlockSpec((1, bq, W), q_index),
                          pl.BlockSpec((1, chunk, W), kv_index),
                          pl.BlockSpec((1, chunk, W), kv_index)],
                out_specs=pl.BlockSpec((1, bq, W), lambda b, g, qi, kc, lens: (b, qi, g)),
                scratch_shapes=[pltpu.VMEM((P, bq, LANES), jnp.float32),
                                pltpu.VMEM((P, bq, LANES), jnp.float32),
                                pltpu.VMEM((P, bq, W), jnp.float32)],
            ),
            out_shape=jax.ShapeDtypeStruct((B, Sqp, Hp * hd), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=64 * 1024 * 1024),
            interpret=interpret,
            name="flash_attention",
        )(lengths, qf, kf, vf)
        return out.reshape(B, Sqp, Hp, hd)[:, :Sq, :H]
