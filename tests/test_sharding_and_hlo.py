"""Sharding-rule resolution, HLO analyzer, and a small-mesh dry-run smoke."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.common.sharding import default_rules, resolve_spec
from repro.launch import hlo_analysis as H


@pytest.fixture(scope="module")
def mesh():
    # 1-device mesh: rule RESOLUTION logic is mesh-shape independent given
    # divisibility, so we exercise fallbacks with a fake-shaped mesh object
    return jax.make_mesh((1, 1), ("data", "model"))


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


HLO_SAMPLE = """
HloModule test

%body (p: (s32[], f32[8,128])) -> (s32[], f32[8,128]) {
  %p = (s32[], f32[8,128]) parameter(0)
  %w = f32[128,128]{1,0} parameter(1)
  %x = f32[8,128]{1,0} get-tuple-element(%p), index=1
  %dot.1 = f32[8,128]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,128]{1,0} all-reduce(%dot.1), replica_groups={}
  ROOT %t = (s32[], f32[8,128]) tuple(%ar, %ar)
}

ENTRY %main (a: f32[8,128]) -> f32[8,128] {
  %a = f32[8,128]{1,0} parameter(0)
  %ag = f32[128,256]{1,0} all-gather(%a), dimensions={1}
  %while.1 = (s32[], f32[8,128]) while(%a), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"10"}}
  ROOT %out = f32[8,128]{1,0} get-tuple-element(%while.1), index=1
}
"""


class TestHloAnalyzer:
    def test_trip_count_weighting(self):
        st = H.analyze(HLO_SAMPLE)
        # dot: 2*8*128*128 flops, 10 trips
        assert st.flops == pytest.approx(10 * 2 * 8 * 128 * 128)
        # all-reduce in body: 2 * 8*128*4 bytes * 10 trips; all-gather once
        ar = 10 * 2 * 8 * 128 * 4
        ag = 128 * 256 * 4
        assert st.coll_bytes["all-reduce"] == pytest.approx(ar)
        assert st.coll_bytes["all-gather"] == pytest.approx(ag)

    def test_kernel_scope_excluded_from_bytes(self):
        txt = HLO_SAMPLE.replace(
            "rhs_contracting_dims={0}",
            'rhs_contracting_dims={0}, metadata={op_name="jit(f)/fusedkernel_flash_attention/dot"}',
        )
        st0 = H.analyze(HLO_SAMPLE)
        st1 = H.analyze(txt)
        assert st1.hbm_bytes < st0.hbm_bytes
        assert st1.flops == st0.flops  # flops still counted


@pytest.mark.slow
def test_small_mesh_dryrun_lowering(mesh):
    """End-to-end lowering of a reduced arch on a real (1,1) mesh: the same
    build path the production dry-run uses."""
    from repro.common.config import InputShape
    from repro.launch.workload import build_steps
    from repro.configs import get_config

    cfg = get_config("yi-34b").reduced()
    shape = InputShape("tiny_train", 32, 4, "train")
    built = build_steps(cfg, shape, mesh=mesh)
    with mesh:
        lowered = jax.jit(built["step"], in_shardings=built["arg_shardings"],
                          out_shardings=built["out_shardings"]).lower(*built["arg_specs"])
        compiled = lowered.compile()
    assert compiled.cost_analysis() is not None
    stats = H.analyze(compiled.as_text())
    assert stats.flops > 0


def test_dryrun_prefill_attention_is_blocked_and_scoped(mesh):
    """Off TPU the dry-run's prefill keeps blocked attention: its working set
    stays far below one layer's (B, H, S, S) float32 scores, and it carries
    the ``fusedkernel_flash_attention`` scope that the roofline reads."""
    from repro.common.config import InputShape
    from repro.configs import get_config
    from repro.launch.workload import build_steps

    cfg = get_config("qwen2.5-7b").reduced()
    B, S = 2, 4096
    built = build_steps(cfg, InputShape("tiny_prefill", S, B, "prefill"), mesh=mesh)
    with mesh:
        compiled = jax.jit(built["step"], in_shardings=built["arg_shardings"],
                           out_shardings=built["out_shardings"]).lower(
                               *built["arg_specs"]).compile()
    assert 'fusedkernel_flash_attention' in compiled.as_text()
    scores = B * cfg.n_heads * S * S * 4
    assert compiled.memory_analysis().temp_size_in_bytes < scores / 4


def test_tpu_shardable_cfg_padding():
    from repro.launch.workload import tpu_shardable_cfg
    from repro.configs import get_config

    yi = tpu_shardable_cfg(get_config("yi-34b"), 16)
    assert yi.n_heads == 64 and yi.n_kv_heads == 8 and yi.head_dim == 128
    wh = tpu_shardable_cfg(get_config("whisper-large-v3"), 16)
    assert wh.n_heads == 32 and wh.n_kv_heads == 32
    mb = tpu_shardable_cfg(get_config("mamba2-130m"), 16)
    assert mb.ssm_n_heads == 32
    ok = tpu_shardable_cfg(get_config("kimi-k2-1t-a32b"), 16)
    assert ok.n_heads == 64 and ok.n_kv_heads == 8  # unchanged
