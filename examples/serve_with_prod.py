"""End-to-end serving driver (deliverable b): serve a small model with batched
requests, with the ProD predictor driving scheduling + KV reservation.

Pipeline (all real, no stubs):
  1. train the tiny LM on the heavy-tailed toy corpus (a few hundred steps);
  2. collect r repeated generations per training prompt at temperature 0.8
     (the paper's data-collection protocol) and harvest real last-layer
     hidden states from prefill;
  3. build ProD-D targets and train the head;
  4. serve a fresh batched workload through the continuous-batching engine,
     comparing FCFS/max-reserve vs ProD-driven SJF + quantile reservation;
  5. replay the same workload across a 2-replica cluster, comparing the
     load-blind round-robin/max-reserve router against the ProD-aware
     predicted-shortest-queue router with quantile KV reservation;
  6. put the trained head IN the dispatch loop: a PredictorService batches
     the head over arrival windows (one jitted fused call per window) and
     the cluster orders its queues by EDF / least-laxity on the predicted
     q0.9 remaining work;
  7. close the loop: drift the workload mid-stream (outputs grow 1.5x while
     features stay put) and serve it with an OnlineAdapter — adaptive
     conformal reservation calibration + warm-start head refresh + SLO-aware
     admission — against the frozen static head;
  8. page the KV cache: replay an SRTF-preemptive, prefill-expensive engine
     under preempt_mode="recompute" (a preempted victim re-reserves and
     re-prefills from scratch) vs "keep" (it holds its filled pages and
     resumes with only the delta), showing the recompute ticks saved;
  9. share the system prompt: replay the workload with every request
     carrying the same 24-token prefix, private copies vs ref-counted
     shared pages (kv_amplification, prefill ticks skipped on cache hits).

    PYTHONPATH=src python examples/serve_with_prod.py [--train-steps 300]
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.compile_cache import use_compile_cache
from repro.common.config import PredictorConfig, TrainConfig
from repro.configs import get_config
from repro.core import bins as B
from repro.core import targets as T
from repro.core.metrics import mae, noise_radius
from repro.core.predictor import train_predictor
from repro.data.pipeline import batch_iterator, make_lm_dataset
from repro.data.tokenizer import N_TOPICS, ToyTokenizer
from repro.models.model_zoo import Runtime, build_model
from repro.serving.adaptation import (AdaptationConfig, AdmissionController,
                                      OnlineAdapter, coverage_of)
from repro.serving.cluster import Cluster
from repro.serving.engine import RealEngine, ReplicaSpec, SimEngine
from repro.serving.predictor import PredictorService
from repro.serving.request import Request
from repro.serving.scheduler import Policy
from repro.training.trainer import train_loop


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--train-steps", type=int, default=300)
    ap.add_argument("--n-prompts", type=int, default=64)
    ap.add_argument("--r", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=96)
    ap.add_argument("--n-serve", type=int, default=96)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    # -- 1. train the served LM ---------------------------------------------
    cfg = get_config("tiny-lm").with_overrides(dtype="float32")
    model = build_model(cfg)
    tcfg = TrainConfig(lr=3e-3, warmup_steps=10, decay_steps=args.train_steps,
                       seed=args.seed)
    ds = make_lm_dataset(2048, 96, seed=args.seed)
    print(f"[1/9] training tiny-lm for {args.train_steps} steps ...")
    state = train_loop(model, tcfg, batch_iterator(ds, 16, seed=args.seed),
                       args.train_steps, rt=Runtime.local(), log_every=100)

    # -- 2. repeated-sampling data collection --------------------------------
    print(f"[2/9] collecting {args.r} generations x {args.n_prompts} prompts ...")
    eng = RealEngine(model, state.params, max_new=args.max_new, temperature=0.8)
    rng = np.random.default_rng(args.seed)
    tok = ToyTokenizer()
    prompts = np.zeros((args.n_prompts, 6), np.int32)
    for i in range(args.n_prompts):
        prompts[i] = tok.prompt(rng, int(rng.integers(0, N_TOPICS)), n_style=4)
    plens = np.full(args.n_prompts, 6)
    t0 = time.time()
    lens, phi = eng.repeated_sampling(prompts, plens, r=args.r, seed=args.seed)
    nr = noise_radius(jnp.asarray(lens))
    print(f"      lengths: median={np.median(lens):.0f} "
          f"max/med={np.max(lens)/max(np.median(lens),1):.2f} "
          f"noise radius={nr:.2f}  ({time.time()-t0:.0f}s)")

    # -- 3. train the ProD-D head on REAL hidden states ----------------------
    print("[3/9] training ProD-D head on the served model's hidden states ...")
    pcfg = PredictorConfig(n_bins=24, bin_max=float(lens.max() + 8), epochs=40,
                           batch_size=32)
    edges = B.make_edges(pcfg.n_bins, pcfg.bin_max)
    tgt = T.dist_target(jnp.asarray(lens, jnp.float32), edges)
    pred = train_predictor(jax.random.PRNGKey(args.seed + 1), jnp.asarray(phi),
                           tgt, pcfg, edges)
    est = pred.predict(jnp.asarray(phi))
    print(f"      in-sample MAE vs prompt medians: "
          f"{mae(est, jnp.asarray(np.median(lens, axis=1))):.2f} "
          f"(noise radius {nr:.2f})")

    # -- 4. serve a fresh workload with ProD scheduling ----------------------
    print(f"[4/9] serving {args.n_serve} batched requests ...")
    arrivals = np.cumsum(rng.exponential(1.5, args.n_serve))
    fresh = rng.integers(0, args.n_prompts, args.n_serve)
    reqs = []
    for i, (j, t) in enumerate(zip(fresh, arrivals)):
        draw = int(lens[j, rng.integers(0, args.r)])  # a fresh-ish realization
        reqs.append(Request(rid=i, arrival=float(t), prompt_len=6,
                            true_len=draw, phi=phi[j]))
    for pol in (Policy("fcfs", "max", max_seq_len=args.max_new),
                Policy("sjf_pred", "quantile", quantile=0.9,
                       max_seq_len=args.max_new)):
        st = SimEngine(max_slots=8, kv_budget=4 * (6 + args.max_new),
                       policy=pol, predictor=pred).run(reqs)
        print(f"      {st.policy:20s} mean_lat={st.mean_latency:7.1f} "
              f"p90={st.p90_latency:7.1f} waste={st.kv_waste_ratio:.3f} "
              f"thr={st.throughput:.2f}")

    # -- 5. heterogeneous cluster replay with the trained ProD head ----------
    # a fast large replica next to a slow small one, per-request SLOs, and
    # periodic ProD-aware work stealing: the full prediction-aware stack
    print("[5/9] replaying across a heterogeneous 2-replica cluster "
          "(speed 2x+1x, SLOs, work stealing) ...")
    specs = (ReplicaSpec(4, 2 * (6 + args.max_new), speed=2,
                         prefill_tokens_per_step=8),
             ReplicaSpec(2, 6 + args.max_new, speed=1,
                         prefill_tokens_per_step=4))
    # tiered SLOs: alternate interactive (tight) / standard / batch (loose)
    # classes, so deadline-aware orderings have real urgency differences
    for i, r in enumerate(reqs):
        r.deadline = r.arrival + (2.0 + 2.0 * (i % 3)) * args.max_new
    for router, pol, reb in (
            ("round_robin", Policy("fcfs", "max", max_seq_len=args.max_new),
             0),
            ("psq", Policy("fcfs", "quantile", quantile=0.9,
                           max_seq_len=args.max_new), 25)):
        cl = Cluster(specs, pol, router=router, predictor=pred,
                     rebalance_every=reb, steal="quantile")
        st = cl.run(reqs)
        label = f"steal@{reb}" if reb else "no-steal"
        print(f"      {st.router:12s}+{st.policy:14s} {label:9s} "
              f"p50={st.p50_latency:7.1f} p99={st.p99_latency:7.1f} "
              f"viol={st.slo_violations} t/o={st.timed_out} "
              f"goodput={st.goodput:.2f} stolen={st.stolen} "
              f"balance={st.balance:.2f}")

    # -- 6. predictor service in the dispatch loop ---------------------------
    # the SAME trained head, now served through the batched jitted
    # PredictorService, driving deadline-aware queue orderings
    print("[6/9] predictor-in-the-loop: batched dispatch-time inference + "
          "deadline-aware ordering ...")
    for order in ("fcfs", "edf", "laxity"):
        svc = PredictorService(pred, window=8.0)
        pol = Policy(order, "quantile", quantile=0.9,
                     max_seq_len=args.max_new)
        st = Cluster(specs, pol, router="psq", predictor=svc,
                     rebalance_every=25, steal="quantile").run(reqs)
        srow = svc.stats.row()
        print(f"      order={order:7s} p50={st.p50_latency:7.1f} "
              f"p99={st.p99_latency:7.1f} viol={st.slo_violations} "
              f"t/o={st.timed_out} goodput={st.goodput:.2f} "
              f"[{srow['batches']} fused batches, mean "
              f"{srow['mean_batch']:.1f} reqs, hit rate {srow['hit_rate']:.2f}]")

    # -- 7. online adaptation under drift ------------------------------------
    # mid-stream regime change: outputs grow 1.5x while the hidden-state
    # features stay put, so the frozen head silently under-reserves. The
    # OnlineAdapter steers the reservation quantile to its coverage target
    # (ACI), warm-start re-fits the head on observed completions, and the
    # AdmissionController rejects SLO-infeasible requests at enqueue. A
    # longer workload (3x the serve set, switch after the first third) gives
    # the feedback loop room to act; coverage is scored on the settled last
    # third.
    print("[7/9] online adaptation: mid-stream 1.5x output drift, static vs "
          "adaptive-conformal + refresh ...")
    n_ad = 3 * args.n_serve
    arr2 = np.cumsum(rng.exponential(1.5, n_ad))
    picks = rng.integers(0, args.n_prompts, n_ad)
    t_switch = float(arr2[n_ad // 3])
    t_tail = float(arr2[2 * n_ad // 3])
    drift_reqs = []
    for i, (j, t) in enumerate(zip(picks, arr2)):
        draw = int(lens[j, rng.integers(0, args.r)])
        if t >= t_switch:
            draw = int(min(args.max_new, round(1.5 * draw)))
        drift_reqs.append(Request(
            rid=i, arrival=float(t), prompt_len=6, true_len=draw, phi=phi[j],
            deadline=float(t) + (2.0 + 2.0 * (i % 3)) * args.max_new))
    for label, gamma, refresh in (("static", 0.0, False),
                                  ("conformal+refresh", 0.05, True)):
        acfg = AdaptationConfig(
            target_coverage=0.9, gamma=gamma, window=64, every=8,
            refresh_every=0.25 * t_switch if refresh else 0.0,
            refresh_min_samples=24, refresh_epochs=30, buffer_size=128)
        adapter = OnlineAdapter(PredictorService(pred, window=8.0), acfg)
        pol = Policy("fcfs", "quantile", quantile=0.9,
                     max_seq_len=args.max_new)
        cl = Cluster(specs, pol, router="psq", predictor=adapter,
                     admission=AdmissionController())
        st = cl.run(drift_reqs)
        cov = coverage_of([r for e in cl.engines for r in e.done],
                          since=t_tail)
        print(f"      {label:18s} settled post-drift coverage={cov:.2f} "
              f"(target 0.90) p99={st.p99_latency:7.1f} "
              f"viol={st.slo_violations} t/o={st.timed_out} "
              f"rejected={st.rejected} refits={st.refreshes} "
              f"q_eff={adapter.q_eff:.3f}")
    # -- 8. paged KV + keep-pages preemption ---------------------------------
    # an SRTF-preemptive single replica with an expensive prefill: under
    # "recompute", every preempted victim re-pays ceil((prompt+progress)/4)
    # prefill ticks on resume; under "keep" it holds the pages it filled
    # (shown by held_peak) and resumes with only the delta reservation
    print("[8/9] paged KV: recompute vs keep-pages preemption "
          "(page_size=4, prefill 4 tok/tick) ...")
    for mode in ("recompute", "keep"):
        pol = Policy("srtf_pred", "quantile", quantile=0.9,
                     max_seq_len=args.max_new, preempt=True,
                     preempt_factor=1.2, preempt_mode=mode)
        spec = ReplicaSpec(2, 4 * (6 + args.max_new) // 4 * 4, speed=2,
                           prefill_tokens_per_step=4, page_size=4)
        st = SimEngine(policy=pol, predictor=pred, spec=spec).run(reqs)
        print(f"      {mode:10s} p50={st.p50_latency:7.1f} "
              f"p99={st.p99_latency:7.1f} preempt={st.preemptions} "
              f"recompute_ticks={st.recompute_ticks} "
              f"held_peak={st.held_peak} occ={st.occupancy:.3f} "
              f"frag={st.frag_ratio:.4f}")
    # -- 9. shared-prefix KV pages -------------------------------------------
    # every request now carries a 24-token system prompt as a shared prefix:
    # with share_prefixes=True one physical copy backs all concurrent
    # requests (ref-counted; kv_amplification > 1) and later admits skip
    # re-prefilling the covered tokens (prefill_saved_ticks)
    print("[9/9] shared system prompt: private copies vs ref-counted "
          "prefix pages ...")
    import dataclasses
    sys_len = 24
    shared_reqs = [dataclasses.replace(r, prompt_len=r.prompt_len + sys_len,
                                       prefix_id="sys/toy",
                                       prefix_len=sys_len) for r in reqs]
    pol = Policy("fcfs", "quantile", quantile=0.9, max_seq_len=args.max_new)
    for share in (False, True):
        spec = ReplicaSpec(4, 4 * (32 + args.max_new), speed=2,
                           prefill_tokens_per_step=4, page_size=4,
                           share_prefixes=share)
        st = SimEngine(policy=pol, predictor=pred, spec=spec).run(shared_reqs)
        print(f"      share_prefixes={str(share):5s} "
              f"p50={st.p50_latency:7.1f} p99={st.p99_latency:7.1f} "
              f"amp={st.kv_amplification:.3f} prefill={st.prefill_ticks} "
              f"saved={st.prefill_saved_ticks} hits={st.prefix_hits}")
    print("done — ProD scheduling/routing/stealing vs prediction-blind "
          "baselines shown above; stage 6 serves the trained head itself "
          "at dispatch time, stage 7 keeps it calibrated while the workload "
          "drifts, stage 8 keeps preempted requests' KV pages so resume "
          "skips the prefill recompute, stage 9 shares one physical copy "
          "of the system prompt across every concurrent request.")


if __name__ == "__main__":
    main()
