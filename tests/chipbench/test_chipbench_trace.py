"""Trace reduction on a small recorded TPU trace.

The fixture ``trace.xplane.pb`` is one traced round on a TPU v5e: the served
path at Qwen2.5-7B's widths cut to one layer, two prompts padded to 128
tokens, three new tokens, then the ProD head, inside the harness's spans
``chipbench.round`` / ``chipbench.generate`` / ``chipbench.head``.
``program0.hlo.txt`` and ``program1.hlo.txt`` are the compiled text of its
prefill and decode programs, compiled for a described v5e; their instruction
names and result shapes are those of the trace's events."""

from pathlib import Path

import numpy as np
import pytest

import chipbench_tiny  # noqa: F401  (puts the repository on the path)
from chipbench import trace

FIX = Path(__file__).resolve().parent / "fixtures"
SCOPES = ("fusedkernel_decode_attention", "fusedkernel_flash_attention")


@pytest.fixture(scope="module")
def reading():
    texts = [(FIX / f"program{k}.hlo.txt").read_text() for k in (0, 1)]
    return trace.reduce(FIX / "trace.xplane.pb", programs=texts, scopes=SCOPES)


def test_busy_and_idle_within_the_window(reading):
    assert reading.n_chips == 1 and reading.n_ops > 100
    assert 0 < reading.busy_s < reading.window_s
    assert 0 < reading.idle_share < 100


def test_both_attention_scopes_are_found_and_fit_in_busy_time(reading):
    for s in SCOPES:
        assert 0 < reading.scope_s[s] < reading.busy_s


def test_top_ops_and_idle_gaps(reading):
    assert 0 < len(reading.top_ops) <= 10
    times = [t for _, t in reading.top_ops]
    assert times == sorted(times, reverse=True) and sum(times) <= reading.busy_s * 1.0001
    assert not any(" while(" in name for name, _ in reading.top_ops)
    names = {n for n, _ in reading.idle_gaps}
    assert names <= {"round", "generate", "head", "outside spans"} and "generate" in names
    idle = sum(t for _, t in reading.idle_gaps)
    assert idle == pytest.approx(reading.window_s - reading.busy_s, rel=1e-6)


def test_hlo_scopes_marks_own_and_fused_op_names():
    text = "\n".join([
        "HloModule jit_step, is_scheduled=true",
        "",
        "%fused_computation.1 (p: f32[4]) -> f32[4] {",
        '  %a = f32[4]{0} exponential(%p), metadata={op_name="jit(step)/fusedkernel_x/exp"}',
        "}",
        "",
        "ENTRY %main (p: f32[4]) -> f32[4] {",
        '  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/other"}',
        '  %copy.2 = f32[4]{0} copy(%fusion.1), metadata={op_name="jit(step)/fusedkernel_y/copy"}',
        '  ROOT %neg.3 = f32[4]{0} negate(%copy.2), metadata={op_name="jit(step)/neg"}',
        "}",
    ])
    module, marks = trace.hlo_scopes(text, ("fusedkernel_x", "fusedkernel_y"))
    assert module == "jit_step"
    assert marks == {"fusion.1": {"fusedkernel_x"}, "copy.2": {"fusedkernel_y"},
                     "a": {"fusedkernel_x"}}


def test_union_of_intervals():
    assert trace._union([0, 1, 5], [2, 3, 6]) == 4.0
    assert trace._union([], []) == 0.0
    s, e = trace._merged(np.array([5, 0, 1]), np.array([6, 2, 3]))
    assert list(s) == [0, 5] and list(e) == [3, 6]
