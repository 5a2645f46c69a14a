"""Shared fixtures. NOTE: no XLA_FLAGS device-count override here — smoke
tests and benches must see the real 1-CPU default."""

import jax
import pytest


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.PRNGKey(0)


@pytest.fixture(scope="session")
def shared_head():
    """One small trained ProD-D head (llama/math, 512 cap, 16 log bins,
    seed 5) shared by test_predictor_in_loop, test_adaptation and
    test_posterior_refine.

    ``fit_trace_head`` is deterministic in ``(cfg.settings(), cfg.view,
    cfg.max_seq_len, seed)`` and independent of the trace pattern/seed, so
    the per-module fixtures those files used to train were bit-identical
    weights — session scope trains them once (~2.5 s saved per extra module).
    """
    from repro.serving.arrivals import TraceConfig
    from repro.serving.predictor import fit_trace_head

    cfg = TraceConfig(n_requests=8, model="llama", scenario="math",
                      max_seq_len=512)
    return fit_trace_head(cfg, n_train=400, r=6, n_bins=16, hidden=32, seed=5)
