"""RealEngine's decode loop on the device, against the host-sampled loop.

``generate`` draws each token inside ``jit_decode_step`` and reads the device
once a step, a step late. ``eager_loop`` below is the plain reference: the
loop that draws on the host, reads every token, EOS flag and finished test
back at once, and stops before decoding the token that finishes the last
row. The two must agree exactly, with and without the per-step reads, at a
sampling and a near-greedy temperature, whether no row, some rows or every
row emits EOS before ``max_new``.

The LM is tiny-lm cut to 2 layers with an 8-token vocabulary, so that the
random weights emit a chosen EOS within a few steps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.serve import random_prompts
from repro.models.model_zoo import build_model
from repro.serving.engine import RealEngine

VOCAB, MAX_NEW = 8, 10
TEMPS = (0.8, 1e-5)


@pytest.fixture(scope="module")
def lm():
    model = build_model(get_config("tiny-lm").with_overrides(n_layers=2,
                                                             vocab_size=VOCAB))
    prompts, plens = random_prompts(3, 6, 14, VOCAB, 0)
    return model, model.init(jax.random.PRNGKey(0)), prompts, plens


def eager_loop(eng, prompts, plens, key, collect_per_step=False, logit_steps=()):
    """``generate``'s outputs and ``decode_steps`` from a loop that samples
    on the host and reads the device three times a step."""
    B, Sp = prompts.shape
    valid = np.arange(Sp)[None, :] < plens[:, None]
    lengths = jnp.asarray(plens, jnp.int32)
    logits, phi, cache = eng._jit_prefill(
        eng.params, {"tokens": jnp.asarray(prompts), "attn_valid": jnp.asarray(valid)},
        lengths)
    cache = eng._grow_cache(cache, Sp + eng.max_new, Sp)
    finished = jnp.zeros(B, bool)
    tokens = np.zeros((B, eng.max_new), np.int32)
    gen_len = np.zeros(B, np.int64)
    step_hidden = np.zeros((B, eng.max_new, eng.model.cfg.d_model), np.float32)
    step_valid = np.zeros((B, eng.max_new), bool)
    step_logits, steps = {}, 0
    for step in range(eng.max_new):
        key, sub = jax.random.split(key)
        nxt = jax.random.categorical(sub, logits / eng.temp, axis=-1)
        nxt = jnp.where(finished, eng.eos, nxt).astype(jnp.int32)
        tokens[:, step] = np.asarray(nxt)
        newly = (~finished) & (nxt == eng.eos)
        finished = finished | (nxt == eng.eos)
        gen_len = np.where(np.asarray(newly), step + 1, gen_len)
        if bool(finished.all()):
            break
        logits, hidden, cache = eng._jit_decode(
            eng.params, {"tokens": nxt, "pos": lengths, "lengths": lengths + 1}, cache)
        lengths = lengths + jnp.where(finished, 0, 1)
        steps += 1
        if step in logit_steps:
            step_logits[step] = np.asarray(logits, np.float32)
        if collect_per_step:
            step_hidden[:, step] = np.asarray(hidden, np.float32)
            step_valid[:, step] = ~np.asarray(finished)
    out = {"lengths": np.where(gen_len == 0, eng.max_new, gen_len),
           "phi": np.asarray(phi), "tokens": tokens, "step_logits": step_logits,
           "step_hidden": step_hidden if collect_per_step else None,
           "step_valid": step_valid if collect_per_step else None}
    return out, steps


def _first_emitted(tokens, v):
    """Per row, the first column holding ``v``, or ``MAX_NEW`` if none."""
    hit = tokens == v
    return np.where(hit.any(1), hit.argmax(1), MAX_NEW)


def _pick_eos(lm, temp, rows):
    """An EOS id for ``rows``: "none" never emitted, "some" emitted by some
    rows but not all, "all" emitted by every row two or more steps before
    ``max_new`` (so the loop stops early and dispatches a step past it)."""
    model, params, prompts, plens = lm
    if rows == "none":
        return VOCAB
    eng = RealEngine(model, params, temperature=temp, max_new=MAX_NEW, eos_id=VOCAB)
    tokens = eng.generate(prompts, plens, jax.random.PRNGKey(3))["tokens"]
    # 0 last: the token buffer starts at 0, so an EOS of 0 would hide a
    # column written after the stop
    for v in (*range(1, VOCAB), 0):
        first = _first_emitted(tokens, v)
        if rows == "some" and (first < MAX_NEW).any() and (first == MAX_NEW).any():
            return v
        if rows == "all" and first.max() <= MAX_NEW - 2 and len(set(first)) > 1:
            return v
    raise AssertionError(f"no EOS id for {rows!r} rows at temperature {temp}")


def _assert_same(got, want):
    assert set(got) == set(want)
    for k in ("lengths", "phi", "tokens", "step_hidden", "step_valid"):
        if want[k] is None:
            assert got[k] is None, k
        else:
            np.testing.assert_array_equal(got[k], want[k], err_msg=k)
    assert sorted(got["step_logits"]) == sorted(want["step_logits"])
    for s, v in want["step_logits"].items():
        np.testing.assert_array_equal(got["step_logits"][s], v, err_msg=f"logits {s}")


@pytest.mark.parametrize("rows", ["none", "some", "all"])
@pytest.mark.parametrize("temp", TEMPS)
def test_device_loop_equals_host_loop(lm, temp, rows):
    model, params, prompts, plens = lm
    eos = _pick_eos(lm, temp, rows)
    eng = RealEngine(model, params, temperature=temp, max_new=MAX_NEW, eos_id=eos)
    key = jax.random.PRNGKey(3)
    for reads in ({}, {"collect_per_step": True, "logit_steps": range(MAX_NEW)}):
        want, steps = eager_loop(eng, prompts, plens, key, **reads)
        got = eng.generate(prompts, plens, key, **reads)
        t = eng.timings[-1]
        _assert_same(got, want)
        assert t["decode_steps"] == steps
        # one read an iteration bar the first, two after the loop, and those
        # asked for in each iteration the loop kept
        per_step = 3 if reads else 0
        kept = steps + 1 if rows == "all" else MAX_NEW
        iters = kept + t["overrun_steps"]
        assert t["host_syncs"] == iters - 1 + 2 + per_step * kept
        if rows == "all":
            assert steps < MAX_NEW - 1
            assert t["overrun_steps"] == 1
            assert (got["tokens"][:, steps + 1:] == 0).all()
            assert (got["lengths"] == _first_emitted(want["tokens"], eos) + 1).all()
        else:
            assert steps == MAX_NEW and t["overrun_steps"] == 0
        if rows == "some":
            assert (got["lengths"] < MAX_NEW).any()


def test_new_temperature_changes_the_draw_without_a_new_trace(lm):
    model, params, prompts, plens = lm
    eng = RealEngine(model, params, temperature=0.8, max_new=MAX_NEW, eos_id=VOCAB)
    key = jax.random.PRNGKey(3)
    drawn = []
    for temp in (0.8, 1e-5, 0.8):
        eng.temp = temp
        drawn.append(eng.generate(prompts, plens, key)["tokens"])
        assert eng._jit_step._cache_size() == 1
    assert (drawn[0] != drawn[1]).any()
    np.testing.assert_array_equal(drawn[0], drawn[2])
    want, _ = eager_loop(eng, prompts, plens, key)
    np.testing.assert_array_equal(drawn[2], want["tokens"])
