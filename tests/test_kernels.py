"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,S,H,KV,hd", [
    (1, 33, 4, 4, 32),    # MHA, ragged seq
    (2, 64, 8, 2, 64),    # GQA
    (1, 96, 4, 1, 16),    # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 17), (False, 0)])
def test_flash_attention_sweep(B, S, H, KV, hd, dtype, causal, window):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, hd), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=32, block_kv=32, impl="interpret")
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,Sc,H,KV,hd", [(2, 100, 8, 2, 64), (1, 40, 4, 4, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(B, Sc, H, KV, hd, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, Sc, KV, hd), dtype)
    v = jax.random.normal(ks[2], (B, Sc, KV, hd), dtype)
    lens = jnp.asarray(np.random.default_rng(0).integers(1, Sc + 1, B), jnp.int32)
    out = ops.decode_attention(q, k, v, lens, block_kv=32, impl="interpret")
    want = ref.decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (2, 53, 3, 8, 16, 16),
    (1, 64, 2, 4, 8, 32),
    (1, 17, 4, 16, 32, 8),
])
def test_ssd_scan_sweep(B, S, H, P, N, chunk):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    a = -dt * jnp.exp(0.3 * jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    y, h = ops.ssd_scan(x, dt, a, Bm, Cm, chunk=chunk, impl="interpret")
    y2, h2 = ref.ssd_scan_ref(x, dt, a, Bm, Cm)
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
