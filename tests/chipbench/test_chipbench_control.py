"""The comparison that decides ``correct`` fails what it has to fail.

At a tiny size on the CPU: the control — the reference in the program's
place at a lower precision — comes out as not correct on every number, and
so does a run whose timed path is broken underneath: a token altered where
it is produced, a decode step that returns its cache unchanged, the head
handed another token's hidden state, a length quantile altered where the
head produces it."""

import time

import numpy as np
import pytest

import chipbench_tiny as tiny
from chipbench import bench, check


@pytest.fixture
def cell(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    return tiny.make(tmp_path)


def run(cell, **kw):
    data, spec = cell
    return bench.run_cell(tiny.CELL, 11, 0.5, False, time.perf_counter(),
                          impl="interpret", data=data, spec_path=spec, **kw)


def test_control_is_not_correct(cell):
    res = run(cell, controls=("fp8",))
    assert res["correct"], res["checks"]
    for k in check.NAMES:
        assert res["controls"]["fp8"][k] > res["checks"][k]["limit"], (k, res["controls"])


def _alter_token(monkeypatch):
    from repro.serving.engine import RealEngine

    real = RealEngine.generate

    def generate(self, *a, **kw):
        out = real(self, *a, **kw)
        out["tokens"] = out["tokens"].copy()
        out["tokens"][:, 1] = (out["tokens"][:, 1] + 1) % self.model.cfg.vocab_size
        return out

    monkeypatch.setattr(RealEngine, "generate", generate)


def _shift_phi(monkeypatch):
    from repro.serving.engine import RealEngine

    real = RealEngine.generate

    def generate(self, *a, **kw):
        out = real(self, *a, **kw)
        out["phi"] = np.roll(out["phi"], 1, axis=0)
        return out

    monkeypatch.setattr(RealEngine, "generate", generate)


def _stale_cache(monkeypatch):
    from repro.models.model_zoo import Model

    real = Model.decode_step

    def decode_step(self, params, batch, cache, rt):
        logits, hidden, _ = real(self, params, batch, cache, rt)
        return logits, hidden, cache

    monkeypatch.setattr(Model, "decode_step", decode_step)


def _alter_quantile(monkeypatch):
    from repro.core.predictor import LengthPredictor

    real = LengthPredictor.quantiles

    def quantiles(self, phi, qs, impl="auto"):
        probs, q = real(self, phi, qs, impl=impl)
        return probs, q.at[:, -1].add(5.0)

    monkeypatch.setattr(LengthPredictor, "quantiles", quantiles)


@pytest.mark.parametrize("fault", [_alter_token, _stale_cache, _shift_phi, _alter_quantile],
                         ids=["token_altered", "state_unchanged", "phi_of_another_row",
                              "answer_altered"])
def test_a_broken_timed_path_is_not_correct(cell, monkeypatch, fault):
    fault(monkeypatch)
    res = run(cell)
    assert res["correct"] is False, res["checks"]


def test_sample_holds_the_longest_and_follows_the_seed():
    served = [{"length": n} for n in (3, 9, 4, 9, 1)]
    a = check.sample(served, 3, seed=5)
    assert len(a) == 3 and len(set(a)) == 3 and served[a[0]]["length"] == 9
    assert a == check.sample(served, 3, seed=5)
    assert check.sample([], 3, seed=5) == []
    assert sorted(check.sample(served, 9, seed=1)) == list(range(5))
    assert np.all([served[i]["length"] <= 9 for i in a])
