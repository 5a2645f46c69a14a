"""RealEngine's own spans and counters, on the CPU at the tiny LM's size.

``generate`` writes ``repro.engine.*`` host spans into a profiler trace and
counts its device→host reads in ``timings[-1]["host_syncs"]``. The spans
must change nothing the engine computes, and the trace must hold them nested
as the engine's docstring says."""

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.launch.serve import build_served_model, random_prompts
from repro.serving.engine import SPAN_PREFIX, RealEngine

MAX_NEW = 5
ENGINE = SPAN_PREFIX + "engine."


@pytest.fixture(scope="module")
def served():
    model, params = build_served_model("tiny-lm", 2, 0)
    prompts, plens = random_prompts(3, 8, 20, model.cfg.vocab_size, 0)
    # an EOS id the model cannot sample, so that no row finishes early
    eng = RealEngine(model, params, max_new=MAX_NEW, eos_id=model.cfg.vocab_size)
    return eng, prompts, plens


@pytest.fixture(scope="module")
def traced(served, tmp_path_factory):
    """(output without a profiler, output under one, the trace's program
    spans as (start, end, name, stats), the traced call's timings)."""
    eng, prompts, plens = served
    key = jax.random.PRNGKey(3)
    plain = eng.generate(prompts, plens, key)
    out_dir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(out_dir))
    try:
        under = eng.generate(prompts, plens, key)
    finally:
        jax.profiler.stop_trace()
    timing = eng.timings[-1]
    (path,) = out_dir.rglob("*.xplane.pb")
    spans = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name, dict(ev.stats))
             for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith(SPAN_PREFIX)]
    return plain, under, sorted(spans), timing


def _named(spans, what):
    return [s for s in spans if s[2] == ENGINE + what]


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_profiler_on_and_off_give_identical_outputs(traced):
    plain, under, _, _ = traced
    for k in ("tokens", "lengths", "phi"):
        np.testing.assert_array_equal(plain[k], under[k])


def test_host_syncs_are_three_per_step_when_no_row_finishes_early(served):
    # one read a step, a step late, bar the first; the tokens and lengths
    # once after the loop
    eng, prompts, plens = served
    out = eng.generate(prompts, plens, jax.random.PRNGKey(5))
    t = eng.timings[-1]
    assert (out["lengths"] == MAX_NEW).all()
    assert t["decode_steps"] == MAX_NEW
    assert t["host_syncs"] == t["decode_steps"] + 1
    assert t["overrun_steps"] == 0
    assert {"prefill_s", "decode_s", "decode_steps", "host_syncs",
            "overrun_steps"} <= set(t)


def test_reads_for_per_step_states_and_logits_are_counted(served):
    eng, prompts, plens = served
    eng.generate(prompts, plens, jax.random.PRNGKey(5), collect_per_step=True,
                 logit_steps=(0, 2))
    t = eng.timings[-1]
    # the loop's own, two a step for the states, one a logit step
    assert t["host_syncs"] == (t["decode_steps"] + 1) + 2 * t["decode_steps"] + 2


def test_trace_holds_the_spans_nested(traced):
    _, _, spans, timing = traced
    (gen,) = _named(spans, "generate")
    assert {"B", "Sp", "max_new", "call"} <= set(gen[3])
    assert gen[3]["max_new"] == MAX_NEW and gen[3]["B"] == 3
    for what in ("prefill", "grow_cache", "decode"):
        (s,) = _named(spans, what)
        assert _inside(s, gen), what
    (dec,) = _named(spans, "decode")
    steps = _named(spans, "step")
    assert len(steps) == timing["decode_steps"] == MAX_NEW
    assert [s[3]["step_num"] for s in steps] == list(range(MAX_NEW))
    assert all(_inside(s, dec) for s in steps)
    syncs = _named(spans, "sync")
    assert len(syncs) == timing["host_syncs"] == len(steps) + 1
    # the first step reads nothing; each later one reads the step before it
    assert [sum(_inside(s, st) for s in syncs) for st in steps] == [0] + [1] * (MAX_NEW - 1)
    final = [s for s in syncs if not any(_inside(s, st) for st in steps)]
    assert len(final) == 2 and all(_inside(s, dec) and s[0] > steps[-1][1] for s in final)
    dispatches = _named(spans, "dispatch")
    assert len(dispatches) == MAX_NEW
    assert [sum(_inside(d, st) for d in dispatches) for st in steps] == [1] * MAX_NEW
    names = {s[2] for s in spans}
    assert names == {ENGINE + w for w in ("generate", "prefill", "grow_cache", "decode",
                                          "step", "sync", "dispatch")}


def test_decode_program_has_a_stable_name(served):
    eng, prompts, _ = served
    B = prompts.shape[0]
    sds = jax.ShapeDtypeStruct
    dbatch = {k: sds((B,), np.int32) for k in ("tokens", "pos", "lengths")}
    cache = jax.tree_util.tree_map(lambda s: sds(s.shape, s.dtype),
                                   eng.model.cache_shapes(B, 24))
    text = eng._jit_decode.lower(eng.params, dbatch, cache).as_text()
    assert "@jit_decode_step" in text
