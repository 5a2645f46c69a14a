"""RoPE/M-RoPE properties and the decode cache's layout rules."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import rope


class TestRope:
    def test_relative_property(self):
        """<rope(q,i), rope(k,j)> depends only on i-j."""
        hd = 32
        q = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 1, hd))
        k = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, hd))

        def score(i, j):
            ai = rope.rope_angles(jnp.array([[i]]), hd, 10000.0)
            aj = rope.rope_angles(jnp.array([[j]]), hd, 10000.0)
            qr = rope.apply_rope(q, ai)
            kr = rope.apply_rope(k, aj)
            return float(jnp.sum(qr * kr))

        assert score(5, 3) == pytest.approx(score(105, 103), rel=1e-5)
        assert score(7, 0) == pytest.approx(score(50, 43), rel=1e-5)

    def test_mrope_reduces_to_rope_on_text(self):
        """With t==h==w positions, M-RoPE must equal standard RoPE."""
        hd, B, S = 64, 2, 9
        pos = rope.positions_from_tokens(B, S)
        mpos = rope.text_mrope_positions(B, S)
        a1 = rope.rope_angles(pos, hd, 1e6, use_mrope=False)
        a2 = rope.rope_angles(mpos, hd, 1e6, use_mrope=True)
        np.testing.assert_allclose(np.asarray(a1), np.asarray(a2), rtol=1e-6)

    def test_mrope_sections_sum(self):
        for hd in (64, 128, 96):
            t, h, w = rope.mrope_section(hd)
            assert t + h + w == hd // 2 and min(t, h, w) > 0

    def test_rope_preserves_norm(self):
        x = jax.random.normal(jax.random.PRNGKey(2), (1, 4, 2, 32))
        ang = rope.rope_angles(rope.positions_from_tokens(1, 4), 32, 1e4)
        xr = rope.apply_rope(x, ang)
        np.testing.assert_allclose(np.linalg.norm(np.asarray(x)),
                                   np.linalg.norm(np.asarray(xr)), rtol=1e-5)


class TestWorkloadRules:
    def test_gemma_cache_is_pattern_grouped(self):
        """Ring caches for local layers, full caches only for global layers —
        the memory design that makes 500k-context gemma fit."""
        from repro.configs import get_config
        from repro.models.model_zoo import build_model
        m = build_model(get_config("gemma3-27b"))
        specs = m.cache_specs(batch=1, cache_len=524_288)
        sizes = sorted({s.shape[2] for seg in specs
                        for e in seg.values() for s in e.values()
                        if len(s.shape) == 4})  # (layers, B, S, KV·hd)
        assert sizes == [1024, 524_288]  # local rings + global full
        # ring layers outnumber global layers 5:1
        n_ring = sum(s.shape[0] for seg in specs for e in seg.values()
                     for k, s in e.items() if k == "k" and s.shape[2] == 1024)
        n_full = sum(s.shape[0] for seg in specs for e in seg.values()
                     for k, s in e.items() if k == "k" and s.shape[2] == 524_288)
        assert n_ring == 52 and n_full == 10
