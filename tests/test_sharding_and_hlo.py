"""Sharding-rule resolution, and the host prefill's blocked attention."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.common.sharding import default_rules, resolve_spec


class FakeMesh:
    """Shape-only stand-in for divisibility tests (no devices needed)."""

    def __init__(self, **axes):
        self.axis_names = tuple(axes)
        self.shape = dict(axes)


class TestResolveSpec:
    def test_basic_mapping(self):
        m = FakeMesh(data=16, model=16)
        rules = {"batch": "data", "heads": "model", "seq": None}
        spec = resolve_spec(("batch", "seq", "heads"), (256, 4096, 64), m, rules)
        assert spec == P("data", None, "model")

    def test_divisibility_fallback_replicates(self):
        m = FakeMesh(data=16, model=16)
        rules = {"batch": "data", "kv": "model"}
        # kv=8 not divisible by 16 -> replicated
        spec = resolve_spec(("batch", "kv"), (256, 8), m, rules)
        assert spec == P("data")

    def test_batch_one_replicates(self):
        m = FakeMesh(data=16, model=16)
        rules = {"batch": "data"}
        spec = resolve_spec(("batch",), (1,), m, rules)
        assert spec == P()

    def test_axis_not_reused(self):
        m = FakeMesh(data=16, model=16)
        rules = {"a": "model", "b": "model"}
        spec = resolve_spec(("a", "b"), (64, 64), m, rules)
        assert spec == P("model")  # second claim dropped (trailing None trimmed)

    def test_tuple_axes_partial(self):
        m = FakeMesh(pod=2, data=16, model=16)
        rules = {"batch": ("pod", "data")}
        # 32 divisible by pod*data=32 -> both; 16 only by prefix (pod,)=2? no:
        spec32 = resolve_spec(("batch",), (32,), m, rules)
        assert spec32 == P(("pod", "data"))
        spec2 = resolve_spec(("batch",), (2,), m, rules)
        assert spec2 == P(("pod",))

    def test_default_rules_weights_not_data_sharded(self):
        m = FakeMesh(data=16, model=16)
        rules = default_rules(m)
        assert rules["embed"] is None
        assert rules["opt_embed"] is not None


@pytest.mark.parametrize("arch", ["qwen2.5-7b", "minicpm-2b"])
def test_host_prefill_attention_is_blocked_and_scoped(arch):
    """Off TPU the model's prefill keeps blocked attention: its working set
    stays far below one layer's (B, H, S, S) float32 scores, and it carries
    the ``fusedkernel_flash_attention`` scope that trace readers charge to
    attention."""
    from repro.configs import get_config
    from repro.models.model_zoo import Runtime, build_model

    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    B, S = 2, 4096
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
             "attn_valid": jax.ShapeDtypeStruct((B, S), jnp.bool_)}
    prefill = jax.jit(lambda p, b: model.prefill(p, b, Runtime.local(),
                                                 logits_mode="none"))
    compiled = prefill.lower(model.param_shapes(), batch).compile()
    assert 'fusedkernel_flash_attention' in compiled.as_text()
    scores = B * cfg.n_heads * S * S * 4
    assert compiled.memory_analysis().temp_size_in_bytes < scores / 4
