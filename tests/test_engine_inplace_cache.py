"""RealEngine's decode loop over one K/V cache, donated to each step and
updated in place, on the CPU at a MiniCPM-shaped tiny size.

The model has MiniCPM-2B's shape of block, cut in width and depth: full
multi-head attention (2 query and 2 K/V heads of width 64), tied embeddings,
an odd vocabulary of 1,001 tokens, ``rms_norm_eps`` 1e-5 and 3 layers. Its
weights come from the benchmark's seeded draw, and the plain reference
``chipbench/references/dense_decoder.py`` computes the same model in float32.
"""

import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from chipbench import bench, weights
from chipbench.references import dense_decoder
from repro.launch.serve import random_prompts
from repro.models.model_zoo import build_model
from repro.serving.engine import SPAN_PREFIX, RealEngine

CONFIG = {
    "name": "minicpm_shaped", "source": "test", "reference": "dense_decoder",
    "hidden_size": 128, "intermediate_size": 320, "num_attention_heads": 2,
    "num_key_value_heads": 2, "num_hidden_layers": 3, "vocab_size": 1001,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
    "eos_token_id": 2, "prod_head": {"hidden": 32, "n_bins": 16, "bin_max": 64.0},
}
MAX_NEW = 8
GREEDY = 1e-5
# bf16 program against the float32 reference: at this size the widest gap
# is 1.4-1.6% of the position's largest logit (weight seeds 11-13); a
# token's K/V written one slot off moves the logits by ~40% of it
LOGIT_REL = 0.03


@pytest.fixture(scope="module")
def served():
    entry = bench.load_module("entries", "static_generate")
    W = weights.draw(CONFIG, 11)
    model = build_model(entry.model_config(CONFIG))
    params = entry.program_params(W)
    prompts, plens = random_prompts(3, 6, 19, CONFIG["vocab_size"], 2)
    return W, model, params, prompts, plens


def _engine(served, eos_id, temperature=0.8):
    _, model, params, _, _ = served
    return RealEngine(model, params, temperature=temperature, max_new=MAX_NEW,
                      eos_id=eos_id)


def test_model_is_minicpm_shaped(served):
    cfg = served[1].cfg
    assert cfg.n_heads == cfg.n_kv_heads == 2 and cfg.head_dim == 64
    assert cfg.tie_embeddings and cfg.vocab_size == 1001 and cfg.norm_eps == 1e-5
    # the cache keeps heads and head width merged: (layers, B, S, KV·hd)
    (seg,) = served[1].cache_shapes(4, 32)
    assert seg["layer_0"]["k"].shape == (3, 4, 32, 128)


def test_prefill_then_inplace_decode_matches_the_reference(served):
    W, model, params, prompts, plens = served
    # an EOS the model cannot sample: every row decodes all its steps
    eng = _engine(served, eos_id=CONFIG["vocab_size"])
    B, Sp = prompts.shape
    valid = np.arange(Sp)[None, :] < plens[:, None]
    first, _, _ = eng._jit_prefill(params, {"tokens": jnp.asarray(prompts),
                                            "attn_valid": jnp.asarray(valid)},
                                   jnp.asarray(plens, jnp.int32))
    out = eng.generate(prompts, plens, jax.random.PRNGKey(4),
                       logit_steps=range(MAX_NEW))
    assert eng.timings[-1]["decode_steps"] == MAX_NEW
    assert sorted(out["step_logits"]) == list(range(MAX_NEW))
    for b in range(B):
        P = int(plens[b])
        seq = np.concatenate([prompts[b, :P], out["tokens"][b]])
        # logits after the prompt's last token, then after each served token
        read = np.arange(P - 1, P + MAX_NEW)
        ref, _ = dense_decoder.forward(W, CONFIG, seq, read, P - 1)
        ref = np.asarray(ref)
        got = np.stack([np.asarray(first[b], np.float32)]
                       + [out["step_logits"][s][b] for s in range(MAX_NEW)])
        gap = np.abs(got - ref).max(axis=-1) / np.abs(ref).max(axis=-1)
        assert (gap < LOGIT_REL).all(), gap


def test_the_cache_handed_to_a_step_is_donated(served):
    _, model, params, prompts, plens = served
    eng = _engine(served, eos_id=CONFIG["eos_token_id"])
    B, Sp = prompts.shape
    valid = np.arange(Sp)[None, :] < plens[:, None]
    logits, _, cache = eng._jit_prefill(params, {"tokens": jnp.asarray(prompts),
                                                 "attn_valid": jnp.asarray(valid)},
                                        jnp.asarray(plens, jnp.int32))
    cache = eng._grow_cache(cache, Sp + MAX_NEW, Sp)
    state = {"tokens": jnp.zeros((B, MAX_NEW), jnp.int32),
             "finished": jnp.zeros(B, bool), "gen_len": jnp.zeros(B, jnp.int32),
             "lengths": jnp.asarray(plens, jnp.int32)}
    out = eng._jit_step(params, logits, jax.random.PRNGKey(0), jnp.float32(0.8),
                        state, 0, cache)
    handed = jax.tree_util.tree_leaves(cache)
    assert handed and all(x.is_deleted() for x in handed)
    new_cache = jax.tree_util.tree_leaves(out[4])
    assert [x.shape for x in new_cache] == [x.shape for x in handed]
    # the bare step the benchmark lowers keeps its argument
    kept = eng._jit_decode(params, {"tokens": state["lengths"], "pos": state["lengths"],
                                    "lengths": state["lengths"] + 1}, out[4])
    assert not any(x.is_deleted() for x in new_cache)
    jax.block_until_ready(kept)


def test_a_loop_that_stops_on_eos_returns_the_right_tokens(served):
    _, _, _, prompts, plens = served
    key = jax.random.PRNGKey(9)
    free = _engine(served, eos_id=CONFIG["vocab_size"], temperature=GREEDY)
    full = free.generate(prompts, plens, key)["tokens"]
    # an EOS that the greedy continuation of row 0 emits at its fourth step:
    # every row must stop at its first such token, with the same tokens
    # up to it as the loop that never stops
    eos = int(full[0, 3])
    stop = _engine(served, eos_id=eos, temperature=GREEDY)
    out = stop.generate(prompts, plens, key)
    for b in range(len(plens)):
        hits = np.flatnonzero(full[b] == eos)
        n = int(hits[0]) + 1 if hits.size else MAX_NEW
        assert out["lengths"][b] == n
        np.testing.assert_array_equal(out["tokens"][b, :n], full[b, :n])
    assert out["lengths"][0] <= 4
    # the token that finishes the last row is never decoded
    all_stop = all((full[b] == eos).any() for b in range(len(plens)))
    assert stop.timings[-1]["decode_steps"] == (
        max(out["lengths"]) - 1 if all_stop else MAX_NEW)


def test_cache_bytes_counts_the_decode_cache_and_the_grow_span_carries_it(
        served, tmp_path):
    _, model, _, prompts, plens = served
    eng = _engine(served, eos_id=CONFIG["vocab_size"])
    B, Sp = prompts.shape
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.generate(prompts, plens, jax.random.PRNGKey(1))
    finally:
        jax.profiler.stop_trace()
    want = sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in
               jax.tree_util.tree_leaves(model.cache_shapes(B, Sp + MAX_NEW)))
    # K and V, 3 layers, B rows, Sp + MAX_NEW slots, 2 heads of 64, bf16
    assert want == 2 * 3 * B * (Sp + MAX_NEW) * 128 * 2
    assert eng.timings[-1]["cache_bytes"] == want
    (path,) = tmp_path.rglob("*.xplane.pb")
    grows = [dict(ev.stats) for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:") for line in plane.lines
             for ev in line.events if ev.name == SPAN_PREFIX + "engine.grow_cache"]
    assert len(grows) == 1 and int(grows[0]["cache_bytes"]) == want


def test_minicpm_2b_cell_cache_bytes():
    """The cell's decode cache from the published widths: 8 rows of
    1,024 + 256 slots at 368,640 B a token."""
    from repro.configs import get_config

    model = build_model(get_config("minicpm-2b"))
    shapes = jax.tree_util.tree_leaves(model.cache_shapes(8, 1024 + 256))
    assert sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in shapes) == 3_774_873_600


def test_a_dropped_engine_frees_its_parameters_at_once(served):
    """No reference cycle holds an engine, and so its parameters, after the
    last reference goes: a process that builds one per run (the benchmark's
    control) would otherwise hold a model's weights per run until the
    garbage collector's next pass."""
    eng = _engine(served, eos_id=CONFIG["vocab_size"])
    _, _, _, prompts, plens = served
    eng.generate(prompts, plens, jax.random.PRNGKey(2))
    ref = weakref.ref(eng)
    del eng
    assert ref() is None
