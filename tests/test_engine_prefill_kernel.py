"""The served prefill's attention through the Pallas flash kernel (interpret
mode on the CPU) against the blocked XLA attention that the prefill runs off
TPU, on the tiny LM: hd 32, so four heads share a lane group, and GQA 4/2."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.flash_attention import live_block_count, prefill_blocks
from repro.launch.serve import build_served_model
from repro.serving.engine import RealEngine

SP = 600                          # two q blocks of 512 at this padded length
PLENS = np.array([37, 300, 600])  # ragged: one row short, one full
TOL = dict(rtol=2e-2, atol=2e-2)  # the bf16 tolerance of test_kernels.py


@pytest.fixture(scope="module")
def served():
    model, params = build_served_model("tiny-lm", 2, 0)
    rng = np.random.default_rng(0)
    prompts = rng.integers(1, model.cfg.vocab_size, (len(PLENS), SP)).astype(np.int32)
    prompts[np.arange(SP)[None, :] >= PLENS[:, None]] = 0
    return model, params, prompts


def _prefill(served):
    model, params, prompts = served
    eng = RealEngine(model, params, max_new=2)
    valid = np.arange(SP)[None, :] < PLENS[:, None]
    logits, phi, _ = eng._jit_prefill(
        params, {"tokens": jnp.asarray(prompts), "attn_valid": jnp.asarray(valid)},
        jnp.asarray(PLENS, jnp.int32))
    return np.asarray(logits, np.float32), np.asarray(phi, np.float32)


def test_prefill_through_the_kernel_matches_blocked_attention(served, monkeypatch):
    with monkeypatch.context() as m:
        m.setattr(ops, "resolve", lambda impl: "interpret")
        logits, phi = _prefill(served)
    want_logits, want_phi = _prefill(served)
    assert np.isfinite(logits).all() and np.isfinite(phi).all()
    np.testing.assert_allclose(phi, want_phi, **TOL)
    np.testing.assert_allclose(logits, want_logits, **TOL)


def test_ragged_prompts_leave_fewer_live_blocks_than_the_full_grid(served, monkeypatch):
    model, params, prompts = served
    monkeypatch.setattr(ops, "resolve", lambda impl: "interpret")
    eng = RealEngine(model, params, max_new=2)
    eng.generate(prompts, PLENS, jax.random.PRNGKey(1))
    t = eng.timings[-1]
    assert t["prefill_attn_blocks"] < t["prefill_attn_blocks_all"]
    want = live_block_count(PLENS, SP, *prefill_blocks(SP))
    assert (t["prefill_attn_blocks"], t["prefill_attn_blocks_all"]) == want
