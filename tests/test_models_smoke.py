"""Per-architecture smoke tests: REDUCED config (2 layers, d_model<=512,
<=4 experts), one forward/train step + prefill/decode coherence on CPU."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.models.model_zoo import Runtime, build_model, last_token_hidden

RT = Runtime.local()
# capacity dispatch drops the tokens an expert cannot fit, and the capacity
# grows with the tokens in the call: a B·S-token forward can drop what a
# B-token decode step keeps, so the two are compared with no drops at all
RT_NODROP = Runtime(capacity_factor=0.0)


@functools.lru_cache(maxsize=None)
def _reduced_model(arch):
    """One build+init per arch, shared across this module's tests — param
    init was re-paid three times per arch and is pure given the fixed key."""
    cfg = get_config(arch).reduced().with_overrides(dtype="float32")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


@functools.lru_cache(maxsize=None)
def _jit_prefill(arch, rt=RT):
    """Jit-cached prefill per arch: XLA-compiling the 2-layer graph once is
    cheaper than eager op-by-op dispatch, and reuses across tests."""
    _, m, _ = _reduced_model(arch)
    return jax.jit(lambda p, b: m.prefill(p, b, rt))


@functools.lru_cache(maxsize=None)
def _jit_train_step(arch, B, S):
    """Shared jitted train-step per arch. The backward graph is the single
    biggest tier-1 compile cost (~6–15 s per arch at default settings), and
    this test only asserts loss/grad finiteness — so compile at XLA
    optimization level 0: ~2x faster to build, same graph semantics, and the
    cache keeps any future caller from re-paying it."""
    cfg, m, _ = _reduced_model(arch)
    batch = _batch_for(cfg, jax.random.PRNGKey(1), B, S)
    fn = jax.jit(jax.value_and_grad(lambda p: m.loss(p, batch, RT)[0]),
                 compiler_options={"xla_backend_optimization_level": "0"})
    return fn


def _batch_for(cfg, key, B, S):
    batch = {"tokens": jax.random.randint(key, (B, S), 1, cfg.vocab_size)}
    if cfg.family == "encdec":
        batch["enc_embeds"] = jax.random.normal(
            key, (B, cfg.encoder_seq, cfg.d_model)) * 0.02
    if cfg.family == "vlm":
        from repro.models.frontend import vlm_embeds
        emb, pos = vlm_embeds(key, cfg, B, S, n_patches=8)
        batch["embeds"] = emb
        batch["positions"] = pos
    return batch


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_reduced_train_step(arch):
    cfg, m, params = _reduced_model(arch)
    assert cfg.n_layers == 2 and cfg.d_model <= 512
    if cfg.family == "moe":
        assert cfg.n_experts <= 4
    B, S = 2, 16
    # one jitted value_and_grad: XLA-compiling the 2-layer graph is several
    # times cheaper than dispatching loss + grad op-by-op in eager mode
    loss, grads = _jit_train_step(arch, B, S)(params)
    assert np.isfinite(float(loss)), arch
    gnorm = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree_util.tree_leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0, arch


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_reduced_prefill_shapes_and_phi(arch):
    cfg, m, params = _reduced_model(arch)
    B, S = 2, 12
    batch = _batch_for(cfg, jax.random.PRNGKey(1), B, S)
    logits, hidden, cache, aux = _jit_prefill(arch)(params, batch)
    assert hidden.shape == (B, S, cfg.d_model)
    assert logits.shape == (B, S, cfg.vocab_size)
    phi = last_token_hidden(hidden, jnp.full((B,), S))
    assert phi.shape == (B, cfg.d_model)
    assert bool(jnp.all(jnp.isfinite(phi)))


@pytest.mark.parametrize("arch", ["yi-34b", "gemma3-27b", "mamba2-130m",
                                  "zamba2-1.2b", "kimi-k2-1t-a32b",
                                  "whisper-large-v3", "qwen2-vl-2b",
                                  # the served configurations, the paper's
                                  # llama3-8b, and one of each remaining kind
                                  "qwen2.5-7b", "minicpm-2b", "llama3-8b",
                                  "granite-20b", "qwen3-moe-235b-a22b",
                                  "tiny-lm"])
def test_decode_matches_forward(arch):
    """prefill(S) + decode_step(token S) == forward(S+1) at position S."""
    cfg, m, params = _reduced_model(arch)
    B, S = 2, 20
    key = jax.random.PRNGKey(1)
    toks = jax.random.randint(key, (B, S + 1), 1, cfg.vocab_size)
    full = {"tokens": toks}
    pre = {"tokens": toks[:, :S]}
    if cfg.family == "encdec":
        enc = jax.random.normal(key, (B, cfg.encoder_seq, cfg.d_model)) * 0.02
        full["enc_embeds"] = enc
        pre["enc_embeds"] = enc
    if cfg.family == "vlm":
        from repro.models.rope import text_mrope_positions
        full["positions"] = text_mrope_positions(B, S + 1)
        pre["positions"] = text_mrope_positions(B, S)
    jp = _jit_prefill(arch, RT_NODROP)
    lg_full, _, _, _ = jp(params, full)
    _, _, cache, _ = jp(params, pre)

    def grow(x):
        # caches keep the sequence on axis 2: (layers, B, S, ·)
        if x.ndim >= 3 and x.shape[2] == S:
            pad = [(0, 0)] * x.ndim
            pad[2] = (0, 8)
            return jnp.pad(x, pad)
        return x

    cache = jax.tree_util.tree_map(grow, cache)
    dbatch = {"tokens": toks[:, S], "pos": jnp.full((B,), S, jnp.int32),
              "lengths": jnp.full((B,), S + 1, jnp.int32)}
    lg_d, _, _ = m.decode_step(params, dbatch, cache, RT_NODROP)
    scale = float(jnp.max(jnp.abs(lg_full[:, S]))) + 1.0
    np.testing.assert_allclose(np.asarray(lg_d), np.asarray(lg_full[:, S]),
                               atol=2e-3 * scale, rtol=1e-3)


def test_moe_capacity_matches_dense_reference():
    from repro.models.layers import init_tree, mlp_apply
    from repro.models.moe import moe_apply, moe_spec
    cfg = get_config("qwen3-moe-235b-a22b").reduced().with_overrides(dtype="float32")
    p = init_tree(jax.random.PRNGKey(2), moe_spec(cfg), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 7, cfg.d_model))
    out, aux = moe_apply(p, x, cfg, capacity_factor=0.0)  # full capacity
    xf = x.reshape(-1, cfg.d_model)
    logits = xf @ p["router"]
    pr = jax.nn.softmax(logits, -1)
    tp, ti = jax.lax.top_k(pr, cfg.n_experts_per_token)
    cw = tp / tp.sum(-1, keepdims=True)
    want = jnp.zeros_like(xf)
    for e in range(cfg.n_experts):
        ye = (jax.nn.silu(xf @ p["w_gate"][e]) * (xf @ p["w_up"][e])) @ p["w_down"][e]
        want += ye * jnp.where(ti == e, cw, 0).sum(-1)[:, None]
    np.testing.assert_allclose(np.asarray(out.reshape(-1, cfg.d_model)),
                               np.asarray(want), rtol=1e-4, atol=1e-4)
    assert float(aux) > 0


def test_gemma_layer_plan_pattern():
    from repro.models.transformer import layer_plan
    cfg = get_config("gemma3-27b")
    plan = layer_plan(cfg)
    assert plan[0].kinds == ("local",) * 5 + ("full",)
    assert plan[0].n_blocks == 10
    assert plan[1].kinds == ("local", "local")
    total = sum(len(s.kinds) * s.n_blocks for s in plan)
    assert total == cfg.n_layers


def test_zamba_hybrid_plan():
    from repro.models.transformer import layer_plan
    cfg = get_config("zamba2-1.2b")
    plan = layer_plan(cfg)
    total_ssm = sum(s.kinds.count("ssm") * s.n_blocks for s in plan)
    assert total_ssm == cfg.n_layers
    assert plan[0].kinds[0] == "shared_attn"
