"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle,
and the served XLA decode attention and chunked SSD scan vs theirs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels import flash_attention
from repro.kernels.flash_attention import live_block_count
from repro.models import attention as attn
from repro.models import ssm


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-5, atol=2e-5)


def _live_pairs_by_mask(lengths, S, block, causal, window):
    """(q block, kv block) pairs of one head holding at least one real query
    that may attend to a real key: what a kernel must compute, and no more."""
    n, pos = -(-S // block), np.arange(S)
    count = 0
    for L in lengths:
        ok = (pos[:, None] < L) & (pos[None, :] < L)
        if causal:
            ok &= pos[None, :] <= pos[:, None]
        if window:
            ok &= (pos[:, None] - pos[None, :]) < window
        for i in range(n):
            for j in range(n):
                count += bool(ok[i * block:(i + 1) * block, j * block:(j + 1) * block].any())
    return count


# rows of one batch: a single token, a length no block divides, the full S
@pytest.mark.parametrize("B,S,H,KV,hd,lengths", [
    pytest.param(1, 33, 4, 4, 32, None, id="1-33-4-4-32"),    # MHA, ragged seq
    pytest.param(2, 64, 8, 2, 64, None, id="2-64-8-2-64"),    # GQA
    pytest.param(1, 96, 4, 1, 16, None, id="1-96-4-1-16"),    # MQA
    pytest.param(3, 80, 7, 1, 128, (1, 45, 80), id="gqa-hd128-rows-1-45-80"),
    pytest.param(3, 80, 4, 4, 64, (80, 1, 45), id="mha-hd64-rows-80-1-45"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 17), (False, 0)])
def test_flash_attention_sweep(B, S, H, KV, hd, lengths, dtype, causal, window):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, hd), dtype)
    lens = None if lengths is None else jnp.asarray(lengths, jnp.int32)
    out = ops.flash_attention(q, k, v, lens, causal=causal, window=window,
                              block_q=32, block_kv=32, impl="interpret")
    want = ref.flash_attention_ref(q, k, v, lens, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))
    if lengths is None:
        return
    past = np.arange(S)[None, :] >= np.asarray(lengths)[:, None]
    assert (np.asarray(out)[past] == 0).all()      # finite, and zero
    live, every = live_block_count(lengths, S, 32, 32, causal=causal, window=window)
    assert live == _live_pairs_by_mask(lengths, S, 32, causal, window)
    assert every == B * 3 * 3


@pytest.mark.parametrize("chunk_blocks", [None, 2, 4])
def test_flash_attention_computes_no_block_past_the_prompt(chunk_blocks, monkeypatch):
    """Keys and values that lie in blocks wholly past a row's prompt are NaN:
    a kernel that multiplied any of them would return NaN (0 · NaN). With
    keys and values cut into chunks of ``chunk_blocks`` kv blocks, chunks
    with no live block are clamped to one that has, and fetch nothing."""
    B, S, H, KV, hd, block = 3, 128, 8, 2, 64, 16
    if chunk_blocks:   # K and V of a chunk, double-buffered, at 128 lanes
        monkeypatch.setattr(flash_attention, "KV_CHUNK_BYTES",
                            chunk_blocks * 4 * block * 128 * 2)
    lengths = np.array([1, 50, 128])
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.bfloat16)
    lens = jnp.asarray(lengths, jnp.int32)
    want = np.asarray(ref.flash_attention_ref(q, k, v, lens), np.float32)
    pos = np.arange(S)
    dead = jnp.asarray((pos[None, :] // block * block >= lengths[:, None])[..., None, None])
    k, v = (jnp.where(dead, jnp.nan, t).astype(t.dtype) for t in (k, v))
    out = np.asarray(ops.flash_attention(q, k, v, lens, block_q=block, block_kv=block,
                                         impl="interpret"), np.float32)
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, want, **_tol(jnp.bfloat16))


@pytest.mark.parametrize("window", [0, 20])
@pytest.mark.parametrize("length", [1, 50, 100, 128])
def test_flash_attention_fetches_only_live_kv_chunks(length, window):
    """Walk the kernel's grid of one row in its order, q blocks then kv chunks
    of 2 blocks, as the pipeline does: a fetch happens where the chunk held
    changes, and each lands on a chunk with a live block of a live q block."""
    S, block, per = 128, 16, 2
    kw = dict(block_q=block, block_kv=block, window=window, xp=np)
    held, fetches = None, 0
    for qi in range(S // block):
        for kc in range(S // (block * per)):
            c = int(flash_attention.kv_chunk(qi, kc, length, chunk_blocks=per, **kw))
            if c == held:
                continue
            held, fetches = c, fetches + 1
            assert qi * block < length, (qi, kc, c)
            lo, _, hi = flash_attention.live_kv_blocks(qi, length, **kw)
            assert lo // per <= c <= (hi - 1) // per, (qi, kc, c, lo, hi)
    live_chunks = {(qi, j // per) for qi in range(S // block) if qi * block < length
                   for j in range(*flash_attention.live_kv_blocks(qi, length, **kw)[::2])}
    assert fetches <= len(live_chunks)


@pytest.mark.parametrize("B,Sc,H,KV,hd", [(2, 100, 8, 2, 64), (1, 40, 4, 4, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(B, Sc, H, KV, hd, dtype):
    """The served decode attention, on a lane-dense cache (heads and head
    width merged), against the oracle on the per-head layout."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, Sc, KV, hd), dtype)
    v = jax.random.normal(ks[2], (B, Sc, KV, hd), dtype)
    lens = jnp.asarray(np.random.default_rng(0).integers(1, Sc + 1, B), jnp.int32)
    out = attn.decode_attention(q[:, None], attn.kv_lanes(k), attn.kv_lanes(v),
                                attn.full_cache_valid(lens, Sc))
    assert out.shape == (B, 1, H, hd) and out.dtype == dtype
    want = ref.decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(out[:, 0], np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (2, 53, 3, 8, 16, 16),
    (1, 64, 2, 4, 8, 32),
    (1, 17, 4, 16, 32, 8),
])
def test_ssd_scan_sweep(B, S, H, P, N, chunk):
    """The chunked SSD scan, with a chunk that divides S or not, against the
    sequential recurrence."""
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    a = -dt * jnp.exp(0.3 * jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    y, h = ssm.ssd_chunked(x, dt, a, Bm, Cm, chunk)
    y2, h2 = ssm.ssd_reference(x, dt, a, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h2), rtol=2e-4, atol=2e-4)


# (5, 1152, ...) tiles d over three 384-row steps of the kernel's grid
@pytest.mark.parametrize("B,d,hid,K", [(7, 32, 16, 8), (33, 96, 64, 32),
                                       (5, 1152, 64, 16)])
def test_prod_head_sweep(B, d, hid, K):
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    phi = jax.random.normal(ks[0], (B, d))
    w1 = jax.random.normal(ks[1], (d, hid)) * 0.2
    b1 = jax.random.normal(ks[2], (hid,)) * 0.01
    w2 = jax.random.normal(ks[3], (hid, K)) * 0.2
    b2 = jnp.zeros(K)
    edges = jnp.linspace(0.0, 512.0, K + 1)
    p1, m1 = ops.prod_head(phi, w1, b1, w2, b2, edges, block_b=8, impl="interpret")
    p2, m2 = ref.prod_head_ref(phi, w1, b1, w2, b2, edges)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), rtol=1e-4, atol=1e-3)


def test_prod_head_median_consistent_with_bins_decoder():
    """Kernel median decode == core.bins.decode_median on the same probs."""
    from repro.core import bins as Bn
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    B, d, hid, K = 16, 24, 16, 12
    phi = jax.random.normal(ks[0], (B, d))
    w1 = jax.random.normal(ks[1], (d, hid)) * 0.3
    w2 = jax.random.normal(ks[2], (hid, K)) * 0.3
    edges = jnp.linspace(0.0, 120.0, K + 1)
    probs, med = ref.prod_head_ref(phi, w1, jnp.zeros(hid), w2, jnp.zeros(K), edges)
    np.testing.assert_allclose(np.asarray(med),
                               np.asarray(Bn.decode_median(probs, edges)),
                               rtol=1e-5, atol=1e-4)
