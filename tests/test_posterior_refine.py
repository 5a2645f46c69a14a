"""Mid-flight posterior length refinement: the ``PosteriorRefiner``
truncate-and-renormalize conditional, its serving integration
(``Policy.refine_every`` quantile refreshes, posterior-keyed ordering, KV
re-reservation), and the PR's bugfix regression (over-runner key collapse in
:func:`~repro.serving.scheduler.quantile_remaining`).

Covers the tentpole acceptance criteria directly:

* hypothesis property sweeps — truncate+renorm is a proper distribution
  (sums to one, zero mass at or below ``t``), posterior quantiles are
  monotone in ``t`` and never below the tokens already emitted, hazard
  corrections stay proper, and ``level_of`` inverts ``quantile``;
* ``refine_every=0`` bit-identity with oracle-driven golden rows (engine +
  cluster), and with the trained head a never-refining run equals the plain
  one, so the legacy paths provably did not move;
* refine-on vec-vs-ref bit-exactness across ``refine_every`` × preempt mode
  × chunked-prefill spec × ordering, and on a stealing cluster;
* calibration — the posterior remaining-work estimate beats the static
  prompt-only estimate in MAE once survival has made the prior stale, on an
  exactly-calibrated heavy-tailed law and through a trained ProD-D head.

Runs under real ``hypothesis`` when installed, else the seeded example sweep
in ``tests/_hypothesis_compat.py``.
"""

import numpy as np
import pytest

from _hypothesis_compat import given, settings, strategies as st

from repro.core.bins import decode_median, make_edges
from repro.core.online import HazardTable, PosteriorRefiner
from repro.serving.arrivals import LatentOracle, TraceConfig, make_trace
from repro.serving.cluster import Cluster
from repro.serving.engine import ReplicaSpec, SimEngine
from repro.serving.predictor import PredictorService
from repro.serving.request import Request
from repro.serving.scheduler import Policy, order_key, quantile_remaining

settings.register_profile("ci", deadline=None, max_examples=12)
settings.load_profile("ci")

EDGES = np.asarray(make_edges(16, 512.0, "log"), np.float64)

# the golden serving configuration (matches the captured pre-change rows)
CFG = TraceConfig(n_requests=200, pattern="poisson", rate=1.6, seed=9,
                  model="llama", scenario="math", max_seq_len=512,
                  slo_factor=6.0, slo_floor=200.0)
POL = Policy("srtf_pred", "quantile", quantile=0.9, max_seq_len=512,
             preempt=True, preempt_factor=1.5, preempt_mode="keep")
SPEC = ReplicaSpec(max_slots=8, kv_budget=4096, speed=2,
                   prefill_tokens_per_step=64, page_size=16)
SPEC_B = ReplicaSpec(4, 2048, speed=1, prefill_tokens_per_step=32,
                     page_size=8)


def _hist(rng, conc=1.0):
    """A random 16-bin histogram (Dirichlet — strictly positive mass)."""
    return rng.dirichlet(np.full(16, float(conc)))


def _refiner(head=None, **kw):
    edges = EDGES if head is None else np.asarray(head.edges, np.float64)
    return PosteriorRefiner(edges, **kw)


# ---------------------------------------------------------------------------
# knob validation
# ---------------------------------------------------------------------------


class TestKnobValidation:
    def test_refine_every_validated(self):
        with pytest.raises(ValueError, match="refine_every"):
            Policy("fcfs", refine_every=-1)
        with pytest.raises(ValueError, match="refine_every"):
            Policy("fcfs", refine_every=2.5)
        assert Policy("fcfs").refine_every == 0
        assert Policy("fcfs", refine_every=16).refine_every == 16

    def test_engine_requires_refiner_when_refining(self):
        pol = Policy("fcfs", "quantile", quantile=0.9, max_seq_len=512,
                     refine_every=16)
        with pytest.raises(ValueError, match="PosteriorRefiner"):
            SimEngine(spec=SPEC, policy=pol)
        # refine off: a refiner is optional and unused
        SimEngine(spec=SPEC, policy=Policy("fcfs"), refiner=_refiner())

    def test_refiner_validates_inputs(self):
        with pytest.raises(ValueError, match="edges"):
            PosteriorRefiner(np.array([4.0]))
        with pytest.raises(ValueError, match="work_quantile"):
            PosteriorRefiner(EDGES, work_quantile=1.0)
        assert _refiner().cap == float(EDGES[-1])


# ---------------------------------------------------------------------------
# truncate-renorm posterior: property sweep
# ---------------------------------------------------------------------------


class TestRefinerProperties:
    @given(st.integers(0, 100_000), st.floats(0.0, 600.0),
           st.floats(0.2, 5.0))
    def test_condition_is_proper_distribution(self, seed, t, conc):
        """P[L ∈ bin | L > t] sums to one, is non-negative, and puts zero
        mass on bins entirely at or below t — for every t, including past
        the support (degenerate point mass at the cap)."""
        rz = _refiner()
        p = _hist(np.random.default_rng(seed), conc)
        cond = rz.condition(p, t)
        assert np.all(cond >= 0.0)
        assert cond.sum() == pytest.approx(1.0, abs=1e-9)
        if rz.survivor(p, t) > 1e-12:
            assert np.all(cond[EDGES[1:] <= t] == 0.0)
        else:
            # past the support: explicit point mass at the cap, never NaN
            assert cond[-1] == 1.0 and np.all(cond[:-1] == 0.0)

    @given(st.integers(0, 100_000), st.floats(0.0, 550.0),
           st.floats(1.0, 80.0))
    def test_quantiles_monotone_in_t_and_never_below_progress(
            self, seed, t, dt):
        """Posterior total-length quantiles are ≥ t, monotone in the CDF
        level, weakly monotone in t (conditioning on longer survival can
        only push the estimate up), and clamped into [t, max(cap, t+1)]."""
        rz = _refiner()
        p = _hist(np.random.default_rng(seed))
        lo_t, hi_t = float(t), float(t + dt)
        q50a, q90a = rz.quantiles(p, lo_t, (0.5, 0.9))
        q50b, q90b = rz.quantiles(p, hi_t, (0.5, 0.9))
        assert q50a <= q90a and q50b <= q90b        # monotone in level
        assert q50a >= lo_t and q90a >= lo_t        # never below progress
        assert q50a <= q50b + 1e-9 and q90a <= q90b + 1e-9  # monotone in t
        cap = max(rz.cap, hi_t + 1.0)
        assert q90b <= cap

    @given(st.integers(0, 100_000))
    def test_t_zero_matches_marginal_decode(self, seed):
        """At t = 0 the posterior is the dispatch histogram: the refined
        median must agree with the marginal CDF-crossing decode
        (:func:`repro.core.bins.decode_median`)."""
        import jax.numpy as jnp

        rz = _refiner()
        p = _hist(np.random.default_rng(seed))
        ours = rz.quantile(p, 0.0, 0.5)
        ref = float(decode_median(jnp.asarray(p[None, :], jnp.float32),
                                  jnp.asarray(EDGES, jnp.float32))[0])
        assert ours == pytest.approx(ref, rel=1e-4)

    @given(st.integers(0, 100_000), st.sampled_from([0.25, 0.5, 0.75, 0.9]))
    def test_level_of_inverts_quantile(self, seed, q):
        """``level_of`` recovers the CDF level a marginal quantile was cut
        at — the effective-level recovery the conformal-on-posterior
        reservation re-cut relies on."""
        rz = _refiner()
        p = _hist(np.random.default_rng(seed))
        v = rz.quantile(p, 0.0, q)
        assert rz.level_of(p, v) == pytest.approx(q, abs=1e-6)

    def test_hazard_identity_correction_is_noop(self):
        """A hazard table whose grid rows equal naive truncate-renorm of its
        own prior corrects by exactly 1 — hazard refinement degrades
        gracefully to pure renormalization when the head learns nothing."""
        rng = np.random.default_rng(4)
        prior = _hist(rng)
        plain = _refiner()
        grid = np.array([0.0, 16.0, 64.0, 256.0])
        hz = HazardTable(ts=grid,
                         probs=np.stack([plain.condition(prior, t)
                                         for t in grid]),
                         prior=prior)
        corrected = PosteriorRefiner(EDGES, hazard=hz)
        p = _hist(rng)
        for t in grid:
            np.testing.assert_allclose(corrected.condition(p, t),
                                       plain.condition(p, t), atol=1e-12)
            assert corrected.quantile(p, t, 0.9) == \
                pytest.approx(plain.quantile(p, t, 0.9), abs=1e-9)

    @given(st.integers(0, 100_000), st.floats(0.0, 600.0))
    def test_hazard_correction_stays_proper_and_clipped(self, seed, t):
        """Arbitrary (even adversarial) hazard rows still yield a proper
        conditional with support above t, and the multiplicative correction
        honors the clip range."""
        rng = np.random.default_rng(seed)
        prior = _hist(rng)
        grid = np.array([0.0, 32.0, 128.0])
        hz = HazardTable(ts=grid, probs=np.stack([_hist(rng)
                                                  for _ in grid]),
                         prior=prior, clip=(0.25, 4.0))
        rz = PosteriorRefiner(EDGES, hazard=hz)
        p = _hist(rng)
        cond = rz.condition(p, t)
        assert cond.sum() == pytest.approx(1.0, abs=1e-9)
        assert np.all(cond >= 0.0)
        if rz.survivor(p, t) > 1e-12:
            assert np.all(cond[EDGES[1:] <= t] == 0.0)
        # correction is bounded: corrected mass within clip × plain mass
        plain = _refiner()._mass(p, t)
        m = rz._mass(p, t)
        live = plain > 0
        assert np.all(m[live] <= plain[live] * 4.0 + 1e-12)
        assert np.all(m[live] >= plain[live] * 0.25 - 1e-12)


# ---------------------------------------------------------------------------
# refine off: bit-identity with the pre-refinement goldens
# ---------------------------------------------------------------------------


class TestGoldenBitIdentity:
    """``refine_every=0`` must leave every legacy number untouched.

    Two legs. The goldens pin the exact rows this configuration gives with
    refinement off, driven by the analytic ``LatentOracle`` (NumPy only, so
    the floats do not drift with the JAX release the way a trained head's
    do). And with the trained ProD-D head service, a run that carries a
    refiner but never refines (reference per-slot path) must equal the plain
    vectorized run without one — the engine as it was before refinement.
    Equality is exact — no tolerance."""

    ENGINE_GOLDEN = dict(
        makespan=1189.0, mean_latency=470.4698508571235,
        p50_latency=434.2981799325181, p90_latency=961.8340729329409,
        p99_latency=1073.4480270563001, mean_wait=405.72660761388016,
        throughput=14.708158116063919, kv_waste_ratio=0.4947386363135703,
        overflow_events=12, peak_reserved=3632, completed=148,
        preemptions=7, timed_out=52, slo_violations=14,
        goodput=12.687973086627418, page_size=16,
        occupancy=0.7456797992010092, frag_ratio=0.030368975605027937,
        prefill_ticks=408, mean_ttft=409.54417518144777,
        p50_ttft=363.6998364762367, p90_ttft=901.4340729329409,
        p99_ttft=977.9414200145728,
    )
    CLUSTER_GOLDEN = dict(
        makespan=1263.0, mean_latency=475.7060821992734,
        p50_latency=421.20782815170895, p90_latency=1005.131993013425,
        p99_latency=1128.2262746362435, mean_wait=378.5211111010074,
        throughput=17.790973871733968, kv_waste_ratio=0.46561093343918747,
        overflow_events=17, completed=173, timed_out=27, slo_violations=20,
        goodput=14.372129849564528, stolen=4, steal_pages=19,
        balance=1.591277258566978, occupancy=0.6882433112298759,
        frag_ratio=0.022201105477205108, prefill_ticks=532,
        mean_ttft=382.5384521414699, p50_ttft=317.4103486182712,
        p90_ttft=862.5746179653437, p99_ttft=978.1888098299285,
    )

    @staticmethod
    def _engine(predictor, **kw):
        return SimEngine(spec=SPEC, policy=POL, predictor=predictor, **kw)

    @staticmethod
    def _cluster(predictor, **kw):
        return Cluster((SPEC, SPEC_B), POL, router="psq", predictor=predictor,
                       rebalance_every=64, steal="quantile", **kw)

    def test_engine_row_unchanged(self, shared_head):
        stats = self._engine(LatentOracle(), vectorized=True).run(make_trace(CFG))
        row = stats.row()
        for k, v in self.ENGINE_GOLDEN.items():
            assert row[k] == v, (k, row[k], v)
        assert stats.refine_events == 0
        assert stats.refine_shrinks == 0 and stats.refine_grows == 0

        plain = self._engine(PredictorService(shared_head, window=8.0),
                             vectorized=True).run(make_trace(CFG))
        idle = self._engine(PredictorService(shared_head, window=8.0),
                            vectorized=False,
                            refiner=_refiner(shared_head)).run(make_trace(CFG))
        assert idle.row() == plain.row()
        assert idle.refine_events == 0

    def test_cluster_row_unchanged(self, shared_head):
        stats = self._cluster(LatentOracle()).run(make_trace(CFG))
        row = stats.row()
        for k, v in self.CLUSTER_GOLDEN.items():
            assert row[k] == v, (k, row[k], v)
        assert stats.refine_events == 0

        plain = self._cluster(PredictorService(shared_head, window=8.0)
                              ).run(make_trace(CFG))
        idle = self._cluster(PredictorService(shared_head, window=8.0),
                             vectorized=False,
                             refiner=_refiner(shared_head)).run(make_trace(CFG))
        assert idle.row() == plain.row()
        assert idle.refine_events == 0


# ---------------------------------------------------------------------------
# refine on: vec-vs-ref bit-exactness
# ---------------------------------------------------------------------------


TRACE_CFG_SMALL = TraceConfig(n_requests=120, pattern="poisson", rate=1.2,
                              seed=5, model="llama", scenario="math",
                              max_seq_len=512, slo_factor=6.0,
                              slo_floor=200.0)


class TestVecRefBitExactness:
    """Refine ticks are evented (like budget-constrained ticks): the
    vectorized leap path must land on exactly the ticks the per-slot
    reference loop refines at, so refined runs stay bit-exact."""

    def _run(self, shared_head, pol, spec, vectorized):
        svc = PredictorService(shared_head, window=8.0)
        eng = SimEngine(spec=spec, policy=pol, predictor=svc,
                        vectorized=vectorized,
                        refiner=_refiner(shared_head))
        stats = eng.run(make_trace(TRACE_CFG_SMALL))
        return stats.row(), stats.refine_events

    @settings(max_examples=8)
    @given(st.sampled_from([1, 4, 16, 48]),
           st.sampled_from(["keep", "recompute"]),
           st.sampled_from(["legacy", "budget", "chunk"]),
           st.sampled_from(["srtf_pred", "laxity"]))
    def test_vec_matches_ref(self, every, pmode, variant, order):
        pol = Policy(order, "quantile", quantile=0.9, max_seq_len=512,
                     preempt=True, preempt_factor=1.5, preempt_mode=pmode,
                     refine_every=every)
        kw = dict(max_slots=8, kv_budget=4096, speed=2, page_size=16)
        if variant == "legacy":
            spec = ReplicaSpec(prefill_tokens_per_step=64, **kw)
        elif variant == "budget":
            spec = ReplicaSpec(step_token_budget=96, **kw)
        else:
            spec = ReplicaSpec(step_token_budget=96,
                               prefill_chunk_tokens=32, **kw)
        head = self._head
        a, ev_a = self._run(head, pol, spec, True)
        b, ev_b = self._run(head, pol, spec, False)
        assert a == b
        assert ev_a == ev_b > 0

    @pytest.fixture(autouse=True, scope="class")
    def _bind_head(self, request, shared_head):
        # @given-wrapped tests cannot take pytest fixtures as extra
        # arguments, so the session head is bound through the class
        request.cls._head = shared_head

    def test_cluster_with_stealing_matches(self, shared_head):
        pol = Policy("srtf_pred", "quantile", quantile=0.9, max_seq_len=512,
                     preempt=True, preempt_factor=1.5, preempt_mode="keep",
                     refine_every=16)
        rows = {}
        for vec in (True, False):
            svc = PredictorService(shared_head, window=8.0)
            cl = Cluster((SPEC, SPEC_B), pol, router="psq", predictor=svc,
                         rebalance_every=64, steal="quantile",
                         vectorized=vec, refiner=_refiner(shared_head))
            stats = cl.run(make_trace(CFG))
            rows[vec] = (stats.row(), stats.refine_events)
        assert rows[True] == rows[False]
        assert rows[True][1] > 0

    def test_refine_on_drains_kv_pool(self, shared_head):
        """After a refined run every page is back in the pool — shrink /
        grow re-reservations never strand pages (engine-level mirror of the
        allocator differential test)."""
        pol = Policy("srtf_pred", "quantile", quantile=0.9, max_seq_len=512,
                     preempt=True, preempt_factor=1.5, preempt_mode="keep",
                     refine_every=8)
        svc = PredictorService(shared_head, window=8.0)
        eng = SimEngine(spec=SPEC, policy=pol, predictor=svc,
                        vectorized=True, refiner=_refiner(shared_head))
        stats = eng.run(make_trace(TRACE_CFG_SMALL))
        assert stats.refine_events > 0
        assert eng.kv.reserved_now == 0 and eng.kv.logical_now == 0
        assert eng.kv.pages_free == eng.kv.pages_total
        assert eng.kv.reserved == {}


# ---------------------------------------------------------------------------
# calibration: posterior beats the prompt-only head once the prior is stale
# ---------------------------------------------------------------------------


class TestCalibration:
    def test_posterior_beats_prompt_only_on_calibrated_law(self):
        """On an exactly calibrated heavy-tailed law (every request carries
        the true histogram), the posterior median's remaining-work MAE
        strictly beats the static prompt-only median from t ≥ 32 on — and
        already at t = 16, since the law's median sits near 25."""
        rng = np.random.default_rng(7)
        lengths = np.clip(rng.lognormal(np.log(25.0), 1.1, size=6000),
                          1.0, 512.0)
        p, _ = np.histogram(lengths, bins=EDGES)
        p = p / p.sum()
        rz = _refiner()
        m0 = rz.quantile(p, 0.0, 0.5)
        for t in (16.0, 32.0, 64.0, 128.0):
            alive = lengths[lengths > t]
            post = np.abs((rz.quantile(p, t, 0.5) - t) - (alive - t)).mean()
            prompt = np.abs(max(m0 - t, 1.0) - (alive - t)).mean()
            assert post < prompt, (t, post, prompt)

    def test_posterior_beats_prompt_only_with_trained_head(self, shared_head):
        """Through the trained ProD-D head on a llama/math trace the
        crossover sits past the predicted medians (~40–190): deep into
        decode (t = 128) the truncated posterior must beat the stale
        dispatch-time median by a wide margin."""
        cfg = TraceConfig(n_requests=400, pattern="poisson", rate=1.6,
                          seed=21, model="llama", scenario="math",
                          max_seq_len=512, slo_factor=6.0, slo_floor=200.0)
        reqs = make_trace(cfg)
        svc = PredictorService(shared_head, window=8.0)
        svc.annotate(reqs, Policy("fcfs", "quantile", quantile=0.9,
                                  max_seq_len=512))
        rz = _refiner(shared_head)
        t = 128.0
        alive = [r for r in reqs if r.true_len > t]
        assert len(alive) > 50
        post = np.mean([abs((rz.quantile(r.pred_probs, t, 0.5) - t)
                            - (r.true_len - t)) for r in alive])
        prompt = np.mean([abs(max(r.predicted_len - t, 1.0)
                              - (r.true_len - t)) for r in alive])
        assert post < prompt * 0.9, (post, prompt)


# ---------------------------------------------------------------------------
# bugfix regression: over-runner key collapse
# ---------------------------------------------------------------------------


class TestOverrunnerRegression:
    """Bugfix: ``quantile_remaining``'s ``max(base - generated, 1.0)`` floor
    collapsed every request that outlived its dispatch quantile onto the
    same key (1.0), so SRTF victim choice, least-laxity ordering, and
    quantile stealing degenerated to tie-break order among over-runners.
    Posterior conditioning keeps them mutually ordered by their tails."""

    def _overrunners(self, n=4):
        rng = np.random.default_rng(3)
        reqs = []
        for i in range(n):
            r = Request(rid=i, arrival=0.0, prompt_len=8, true_len=400,
                        deadline=600.0)
            r.predicted_len = 40.0 + 5 * i
            r.pred_q = 90.0 + 10 * i
            r.reserve_len = r.pred_q
            r.pred_probs = rng.dirichlet(np.ones(16) * (0.5 + i))
            r.generated = 200 + 10 * i       # far past its q0.9
            reqs.append(r)
        return reqs

    def test_overrunner_keys_collapse_without_refiner(self):
        """Pins the pre-fix behavior: with no refiner every over-runner
        keys to exactly the 1.0 floor — indistinguishable."""
        keys = [quantile_remaining(r) for r in self._overrunners()]
        assert keys == [1.0] * len(keys)

    def test_refiner_keeps_overrunner_keys_distinct(self):
        rz = _refiner()
        keys = [quantile_remaining(r, refiner=rz)
                for r in self._overrunners()]
        assert all(k > 1.0 for k in keys)
        assert len(set(keys)) == len(keys)          # mutually ordered
        # each key is the posterior work-quantile of the *remaining* tokens
        for r, k in zip(self._overrunners(), keys):
            want = rz.quantile(r.pred_probs, float(r.generated),
                               rz.work_quantile) - r.generated
            assert k == pytest.approx(want)

    def test_laxity_order_key_uses_posterior(self):
        rz = _refiner()
        keys_off = {order_key(r, "laxity", max_cap=512.0)
                    for r in self._overrunners()}
        keys_on = {order_key(r, "laxity", max_cap=512.0, refiner=rz)
                   for r in self._overrunners()}
        assert len(keys_off) == 1                   # pre-fix: all tied
        assert len(keys_on) == len(self._overrunners())

    def test_normal_runners_unaffected(self):
        """The posterior path only engages on over-runners: a request still
        below its dispatch quantile keys identically with and without the
        refiner."""
        r = Request(rid=0, arrival=0.0, prompt_len=8, true_len=400)
        r.pred_q = 300.0
        r.pred_probs = np.full(16, 1 / 16)
        r.generated = 100
        assert quantile_remaining(r) == \
            quantile_remaining(r, refiner=_refiner()) == 200.0
