"""The program's spans read beside the device's time (``program_spans.py``),
the engine counter's reader, and the harness's reduction left as it was.

``trace.xplane.pb`` is the fixture described in ``test_chipbench_trace.py``:
harness spans only, recorded before the program had spans of its own.
``trace_spans.xplane.pb`` is one traced round recorded the same way on a TPU
v5e (Qwen2.5-7B's widths cut to one layer, two prompts padded to 128 tokens,
three new tokens, then the ProD head, inside ``chipbench.round``) after the
engine gained its ``repro.engine.*`` spans and its program names."""

import warnings
from pathlib import Path

import numpy as np
import pytest

import chipbench_tiny  # noqa: F401  (puts the repository on the path)
from chipbench import bench, program_spans, trace

FIX = Path(__file__).resolve().parent / "fixtures"
SCOPES = ("fusedkernel_decode_attention", "fusedkernel_flash_attention")


@pytest.fixture(scope="module")
def old_reading():
    return program_spans.reduce(FIX / "trace.xplane.pb")


@pytest.fixture(scope="module")
def reading():
    return program_spans.reduce(FIX / "trace_spans.xplane.pb")


def test_gap_goes_to_the_innermost_span_of_either_family():
    spans = [(0, 100, "round"), (10, 90, "generate"),
             (20, 80, "repro.engine.decode"), (30, 50, "repro.engine.step"),
             (40, 45, "repro.engine.sync"), (50, 70, "repro.engine.step")]
    g0 = np.array([-10, 12, 41, 46, 55, 72, 95])
    g1 = np.array([-5, 14, 43, 48, 60, 76, 99])
    got = program_spans.charge_gaps(g0, g1, spans)
    assert got == {"outside spans": 5, "generate": 2, "repro.engine.sync": 2,
                   "repro.engine.step": 2 + 5, "repro.engine.decode": 4, "round": 4}
    assert sum(got.values()) == pytest.approx(float(np.sum(g1 - g0)))


def test_gap_attribution_matches_the_harness_rule_on_equal_spans():
    # two spans of one length around a gap: the first listed wins, as in trace.py
    got = program_spans.charge_gaps([4], [6], [(0, 10, "a"), (0, 10, "b")])
    assert got == {"a": 2.0}
    assert program_spans.charge_gaps([], [], [(0, 1, "a")]) == {}


def test_clock_skew_is_bounded_by_launch_and_completion():
    dev_s = np.array([100.0, 300.0])
    dev_e = np.array([150.0, 320.0])
    launch = dev_s + 40.0             # host launch seen 40 after the device start
    done = dev_e + 30.0               # and completion 30 after its end
    # skew s: launch <= dev_s - s and dev_e - s <= done, so -30 <= s <= -40 fails
    assert program_spans.clock_skew(dev_s, dev_e, launch, done) is None
    done = dev_e + 60.0               # -60 <= s <= -40
    assert program_spans.clock_skew(dev_s, dev_e, launch, done) == pytest.approx((-50.0, 10.0))
    assert program_spans.clock_skew(dev_s, dev_e, launch[:1], done) is None


def test_busy_upto_counts_partial_intervals():
    ms, me = np.array([0.0, 10.0]), np.array([5.0, 20.0])
    got = program_spans._busy_upto(ms, me, [-1.0, 3.0, 7.0, 15.0, 30.0])
    assert list(got) == [0.0, 3.0, 5.0, 10.0, 15.0]


def test_fixture_without_program_spans_keeps_the_harness_gaps(old_reading):
    r = old_reading
    assert r.spans == {} and r.n_chips == 1
    # the device's clock reads about 1.3 ms before the host's in this trace
    assert -1.46e6 <= r.skew_ns <= -1.15e6 and 0 <= r.skew_err_ns <= 0.16e6
    idle = sum(t for _, t in r.idle_gaps)
    assert idle == pytest.approx(r.window_s - r.busy_s, rel=1e-9)
    assert {n for n, _ in r.idle_gaps} <= {"round", "generate", "head", "outside spans"}
    old = trace.reduce(FIX / "trace.xplane.pb")
    assert r.window_s == old.window_s
    assert r.busy_s == pytest.approx(old.busy_s, rel=1e-3)
    assert {"jit_prefill", "jit__lambda"} <= set(r.module_s)
    assert sum(r.module_s.values()) <= r.busy_s * 1.0001


def test_existing_reading_of_the_committed_fixture_is_unchanged():
    texts = [(FIX / f"program{k}.hlo.txt").read_text() for k in (0, 1)]
    r = trace.reduce(FIX / "trace.xplane.pb", programs=texts, scopes=SCOPES)
    assert (r.window_s, r.n_chips, r.n_ops) == pytest.approx((0.034682052, 1, 352))
    assert r.busy_s == pytest.approx(0.008646636, rel=1e-9)
    assert r.scope_s == pytest.approx({"fusedkernel_flash_attention": 1.2866e-05,
                                       "fusedkernel_decode_attention": 5.758e-06})
    assert len(r.top_ops) == 10
    for got, want in zip(r.top_ops, [("jit__lambda:fusion.103", 0.004329478),
                                     ("jit_prefill:fusion.81", 0.001443238),
                                     ("jit__lambda:fusion.44", 0.001086768)]):
        assert got[0] == want[0] and got[1] == pytest.approx(want[1], rel=1e-9)
    assert [n for n, _ in r.idle_gaps] == ["generate", "head"]
    assert [t for _, t in r.idle_gaps] == pytest.approx([0.024483556, 0.00155186],
                                                        rel=1e-9)


def test_program_prefix_is_the_engines():
    from repro.serving.engine import SPAN_PREFIX

    assert program_spans.PROGRAM_PREFIX == SPAN_PREFIX


def test_command_prints_one_json_reading(capsys):
    program_spans.main([str(FIX / "trace.xplane.pb")])
    import json

    line = json.loads(capsys.readouterr().out)
    assert set(line) == {"window_s", "busy_s", "skew_ns", "skew_err_ns", "idle_gaps",
                         "module_s", "spans", "n_chips"}
    with pytest.raises(SystemExit):
        program_spans.main([])


def _run(*timings):
    return bench.Run(cfg={}, device_kind="cpu", setup_s=1.0, window_s=1.0,
                     rounds=[{"timing": t} for t in timings])


def test_host_syncs_reader():
    read = bench.load_module("metrics", "host_syncs_per_step").read
    assert read(_run()) is None
    assert read(_run({"decode_s": 1.0, "decode_steps": 4})) is None
    assert read(_run({"decode_steps": 256, "host_syncs": 768},
                     {"decode_steps": 255, "host_syncs": 768})) == pytest.approx(1536 / 511)
    assert read(_run({"decode_steps": 0, "host_syncs": 3})) is None


def test_program_spans_of_one_round(reading):
    r = reading
    assert r.n_chips == 1 and r.skew_ns is not None and r.skew_err_ns < 0.2e6
    count = {name: v["n"] for name, v in r.spans.items()}
    assert count == {"repro.engine.generate": 1, "repro.engine.prefill": 1,
                     "repro.engine.grow_cache": 1, "repro.engine.decode": 1,
                     "repro.engine.step": 3, "repro.engine.sample": 6,
                     "repro.engine.sync": 9, "repro.engine.dispatch": 3}
    for name, v in r.spans.items():
        assert 0 <= v["idle_s"] <= v["s"] * 1.0001, name
    dec = r.spans["repro.engine.decode"]
    # 18 programs per step (the decode program and 17 small eager ones), and
    # the one the loop's first step waits on
    assert dec["programs"] == 55 and r.spans["repro.engine.step"]["programs"] == 52
    assert dec["idle_s"] == pytest.approx(0.020290136, rel=1e-6)


def test_decode_program_time_by_its_stable_name(reading):
    r = reading
    assert "jit__lambda" not in r.module_s
    assert r.module_s["jit_decode_step"] == pytest.approx(0.006213794, rel=1e-6)
    assert r.module_s["jit_decode_step"] < r.busy_s
    assert sum(r.module_s.values()) <= r.busy_s * 1.0001


def test_idle_inside_generate_is_charged_to_program_spans(reading):
    r = reading
    gaps = dict(r.idle_gaps)
    assert sum(gaps.values()) == pytest.approx(r.window_s - r.busy_s, rel=1e-9)
    program = sum(t for n, t in gaps.items() if n.startswith("repro.engine."))
    assert program >= 0.9 * (program + gaps.get("generate", 0.0))
    assert {"repro.engine.sync", "repro.engine.sample", "repro.engine.dispatch"} <= set(gaps)


def test_harness_reduction_ignores_program_spans():
    r = trace.reduce(FIX / "trace_spans.xplane.pb")
    assert {n for n, _ in r.idle_gaps} <= {"round", "generate", "head", "outside spans"}
    assert sum(t for _, t in r.idle_gaps) == pytest.approx(r.window_s - r.busy_s, rel=1e-6)


def test_unbounded_skew_is_reported_not_silent(monkeypatch):
    path = FIX / "trace_spans.xplane.pb"
    with warnings.catch_warnings():
        warnings.simplefilter("error", program_spans.UncorrectedSkew)
        assert program_spans.reduce(path).skew_ns is not None
    host = program_spans._host

    def one_launch_dropped(prof):
        spans, launches, done = host(prof)
        return spans, sorted(launches)[1:], done

    monkeypatch.setattr(program_spans, "_host", one_launch_dropped)
    with pytest.warns(program_spans.UncorrectedSkew, match="could not be bounded"):
        r = program_spans.reduce(path)
    assert r.skew_ns is None and r.skew_err_ns is None
