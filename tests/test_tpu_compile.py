"""Compile the served path for a described TPU v5e chip, with no chip attached.

The TPU compiler is installed with JAX, and it compiles for a topology that is
described rather than present. These tests catch what interpret mode cannot:
a kernel Mosaic refuses to lower, more VMEM than a kernel may use, a step
program that does not fit the chip's 16 GB. Nothing runs, so they say
nothing about results or times.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file. Keep these tests in this one file, so the worker that runs them is
the only one that loads it.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 1024**3   # one v5e chip

# the shapes chip_smoke.py serves: Qwen2.5-7B widths, 8 prompts right-padded
# to 1024, 128 new tokens
B, SP, MAX_NEW = 8, 1024, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Shape-only arguments on one described chip, compiled with the
    persistent compilation cache off: an executable compiled for a described
    chip cannot be read back without one. The kernels' ``impl="auto"``
    dispatch asks the process's backend, which is the CPU here; it is told
    the TPU's answer, so that the programs compiled are those the chip runs."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.kernels import ops

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    sharding = SingleDeviceSharding(topo.devices[0])

    def shape(s, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(s, dtype, sharding=sharding)

    with pytest.MonkeyPatch.context() as m:
        m.setattr(ops, "_on_tpu", lambda: True)
        yield shape
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _on_chip(tree, shape):
    return jax.tree_util.tree_map(lambda s: shape(s.shape, s.dtype), tree)


def _fits(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)
    assert used < HBM_BYTES, used
    return used


@pytest.mark.parametrize("block_b", [8, 128])
def test_prod_head_kernel_compiles_at_served_width(one_chip, block_b):
    from repro.kernels.prod_head import prod_head_pallas

    f32 = jnp.float32
    d, hidden, K, Q = 3584, 512, 64, 3
    fn = jax.jit(lambda phi, w1, b1, w2, b2, e, q: prod_head_pallas(
        phi, w1, b1, w2, b2, e, qs=q, block_b=block_b))
    compiled = fn.lower(one_chip((block_b, d), f32), one_chip((d, hidden), f32),
                        one_chip((hidden,), f32), one_chip((hidden, K), f32),
                        one_chip((K,), f32), one_chip((K + 1,), f32),
                        one_chip((Q,), f32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_attention_kernel_compiles(one_chip):
    from repro.kernels.decode_attention import decode_attention_pallas

    H, KV, hd, Sc = 28, 4, 128, SP + MAX_NEW
    compiled = jax.jit(decode_attention_pallas).lower(
        one_chip((B, H, hd)), one_chip((B, Sc, KV, hd)),
        one_chip((B, Sc, KV, hd)), one_chip((B,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_kernel_compiles(one_chip):
    from repro.kernels.flash_attention import flash_attention_pallas

    H, KV, hd = 28, 4, 128
    compiled = jax.jit(flash_attention_pallas).lower(
        one_chip((B, SP, H, hd)), one_chip((B, SP, KV, hd)),
        one_chip((B, SP, KV, hd))).compile()
    assert "tpu_custom_call" in compiled.as_text()


# the served prefill's attention at the cells' shapes: (rows, prompts padded
# to, heads, K/V heads, head width)
PREFILL_CELLS = {"qwen25_7b.prefill_long": (4, 4096, 28, 4, 128),
                 "minicpm_2b.decode_long": (8, 1024, 36, 36, 64)}


@pytest.mark.parametrize("cell", sorted(PREFILL_CELLS))
def test_length_aware_flash_kernel_compiles(one_chip, cell):
    from repro.kernels.flash_attention import flash_attention_pallas

    b, s, H, KV, hd = PREFILL_CELLS[cell]
    compiled = jax.jit(flash_attention_pallas).lower(
        one_chip((b, s, H, hd)), one_chip((b, s, KV, hd)),
        one_chip((b, s, KV, hd)), one_chip((b,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def served(one_chip):
    """RealEngine's jitted steps over Qwen2.5-7B widths at 2 layers (the
    layers are scanned, so depth changes no kernel, only the loop count)."""
    from repro.configs import get_config
    from repro.models.model_zoo import build_model
    from repro.serving.engine import RealEngine

    model = build_model(get_config("qwen2.5-7b").with_overrides(n_layers=2))
    params = _on_chip(model.param_shapes(), one_chip)
    return model, params, RealEngine(model, params, max_new=MAX_NEW)


@pytest.fixture(scope="module")
def served_prefill(served, one_chip):
    model, params, eng = served
    batch = {"tokens": one_chip((B, SP), jnp.int32),
             "attn_valid": one_chip((B, SP), jnp.bool_)}
    return eng._jit_prefill.lower(params, batch, one_chip((B,), jnp.int32)).compile()


def test_served_prefill_step_compiles(served, served_prefill):
    model, _, _ = served
    compiled = served_prefill
    _fits(compiled)
    logits, phi, _ = compiled.out_info
    assert logits.shape == (B, model.cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert phi.shape == (B, model.cfg.d_model)


def test_prefill_attention_is_the_kernel_under_its_scope(served_prefill):
    """The trace's reader marks the flash kernel's custom call with the scope
    that ``prefill_attention_roofline`` reads, in the compiled prefill."""
    import re
    import sys
    from pathlib import Path

    root = str(Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from chipbench.trace import hlo_scopes

    text = served_prefill.as_text()
    module, marks = hlo_scopes(text, ("fusedkernel_flash_attention",))
    assert module == "jit_prefill"
    calls = {re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line).group(1)
             for line in text.splitlines() if '"tpu_custom_call"' in line}
    assert any(name in marks for name in calls), (calls, marks)


def test_prefill_attention_relayouts_count_against_the_kernel(served_prefill):
    """The copies that lay q, k and v out for the flash kernel carry its
    scope, so that ``prefill_attention_roofline`` counts their time."""
    import re
    import sys
    from pathlib import Path

    root = str(Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from chipbench.trace import hlo_scopes

    text = served_prefill.as_text()
    _, marks = hlo_scopes(text, ("fusedkernel_flash_attention",))
    defs = dict(re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) = \S+ ([\w-]+)\(", text, re.M))
    (call,) = [line for line in text.splitlines()
               if '"tpu_custom_call"' in line and "flash_attention" in line]
    operands = re.search(r"custom-call\(([^)]*)\)", call).group(1)
    copies = [n for n in re.findall(r"%([\w.\-]+)", operands) if defs.get(n) == "copy"]
    assert copies, operands
    assert all(n in marks for n in copies), (copies, marks)


def test_served_decode_step_compiles(served, one_chip):
    model, params, eng = served
    cache = _on_chip(model.cache_shapes(B, SP + MAX_NEW), one_chip)
    ids = one_chip((B,), jnp.int32)
    compiled = eng._jit_decode.lower(
        params, {"tokens": ids, "pos": ids, "lengths": ids}, cache).compile()
    _fits(compiled)
    logits = compiled.out_info[0]
    assert logits.shape == (B, model.cfg.vocab_size)


def test_served_draw_and_decode_step_compiles(served, one_chip):
    """The program the decode loop runs: the token's draw, then the step."""
    model, params, eng = served
    cache = _on_chip(model.cache_shapes(B, SP + MAX_NEW), one_chip)
    state = {"tokens": one_chip((B, MAX_NEW), jnp.int32),
             "finished": one_chip((B,), jnp.bool_),
             "gen_len": one_chip((B,), jnp.int32),
             "lengths": one_chip((B,), jnp.int32)}
    key = _on_chip(jax.eval_shape(lambda: jax.random.PRNGKey(0)), one_chip)
    compiled = eng._jit_step.lower(
        params, one_chip((B, model.cfg.vocab_size), jnp.float32), key,
        one_chip((), jnp.float32), state, one_chip((), jnp.int32), cache).compile()
    _fits(compiled)
    new_state, _, logits, _, _, done = compiled.out_info
    assert new_state["tokens"].shape == (B, MAX_NEW)
    assert logits.shape == (B, model.cfg.vocab_size)
    assert done.shape == () and done.dtype == jnp.bool_


# the benchmark's cells: (layers, rows, prompts padded to, new tokens)
CELLS = {"minicpm-2b": (40, 8, 1024, 256), "qwen2.5-7b": (14, 32, 512, 256)}


@pytest.mark.parametrize("arch", sorted(CELLS))
def test_decode_loop_step_holds_one_cache_updated_in_place(one_chip, arch):
    """The decode loop's program at a cell's full shapes: its cache argument
    is donated and written in place (aliased whole, no cache-sized
    temporary), and weights, cache and temporaries leave the chip room for
    the prefill's cache beside them."""
    from repro.configs import get_config
    from repro.models.model_zoo import build_model
    from repro.serving.engine import RealEngine

    L, b, sp, new = CELLS[arch]
    model = build_model(get_config(arch).with_overrides(n_layers=L))
    params = _on_chip(model.param_shapes(), one_chip)
    eng = RealEngine(model, params, max_new=new)
    cache = _on_chip(model.cache_shapes(b, sp + new), one_chip)
    state = {"tokens": one_chip((b, new), jnp.int32),
             "finished": one_chip((b,), jnp.bool_),
             "gen_len": one_chip((b,), jnp.int32),
             "lengths": one_chip((b,), jnp.int32)}
    key = _on_chip(jax.eval_shape(lambda: jax.random.PRNGKey(0)), one_chip)
    m = eng._jit_step.lower(
        params, one_chip((b, model.cfg.vocab_size), jnp.float32), key,
        one_chip((), jnp.float32), state, one_chip((), jnp.int32),
        cache).compile().memory_analysis()
    cache_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(cache))
    assert m.alias_size_in_bytes >= cache_bytes
    assert m.temp_size_in_bytes < 0.25e9
    assert m.argument_size_in_bytes + m.temp_size_in_bytes <= 0.6 * HBM_BYTES
