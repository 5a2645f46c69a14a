"""chip_smoke.py and the compile-cache rule, exercised on the CPU.

The script itself refuses to run without a TPU; here its phases run at the
tiny LM's size with the Pallas head in interpret mode, which checks the
control flow and both agreement checks, not the chip."""

import importlib.util
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_to_run_without_a_tpu(chip_smoke):
    with pytest.raises(SystemExit) as e:
        chip_smoke.tpu_devices()
    assert "no TPU found" in str(e.value.code)


def test_phases_and_checks_pass_at_tiny_size(chip_smoke, tmp_path, monkeypatch,
                                             capsys):
    # JAX reads the variable only at start-up, so setting it here keeps the
    # run from switching this process's persistent cache on
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    chip_smoke.run(seed=0, arch="tiny-lm", n_layers=2, n_prompts=4,
                   prompt_len=(8, 24), r=2, max_new=12, impl="interpret")
    out = capsys.readouterr().out
    for phase in ("compile cache:", "model:", "generate:", "compile:",
                  "prefill:", "decode:", "check decode-vs-prefill:",
                  "check head-vs-reference (trained head):",
                  "check head-vs-reference (fresh head):", "memory:"):
        assert phase in out, phase


def test_decode_check_catches_a_wrong_position(chip_smoke, monkeypatch):
    """Shifting the decode logits one step off their prefill position has to
    fail the decode-vs-prefill tolerance."""
    from repro.launch.serve import build_served_model, random_prompts
    from repro.serving.engine import RealEngine

    model, params = build_served_model("tiny-lm", 2, 0)
    prompts, plens = random_prompts(3, 8, 20, model.cfg.vocab_size, 0)
    eng = RealEngine(model, params, max_new=8)
    worst, n = chip_smoke.decode_vs_prefill(eng, prompts, plens, 0, steps=(1, 4))
    assert n > 0 and worst <= chip_smoke.DECODE_REL_TOL
    real = eng.generate

    def shifted(*a, logit_steps=(), **kw):
        out = real(*a, logit_steps=tuple(s + 1 for s in logit_steps), **kw)
        out["step_logits"] = {s - 1: v for s, v in out["step_logits"].items()}
        return out

    monkeypatch.setattr(eng, "generate", shifted)
    worst, _ = chip_smoke.decode_vs_prefill(eng, prompts, plens, 0, steps=(1, 4))
    assert worst > chip_smoke.DECODE_REL_TOL


def test_compile_cache_follows_the_environment(monkeypatch):
    from repro.common import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV, "/elsewhere/cache")
    assert compile_cache.use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before   # JAX reads it itself

    monkeypatch.delenv(compile_cache.ENV)
    try:
        got = compile_cache.use_compile_cache()
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
