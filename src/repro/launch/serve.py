"""Serving launcher: run the continuous-batching engine with ProD scheduling.

Two modes:
  --mode sim   discrete-event simulator over a calibrated scenario workload,
               comparing FCFS/max-reserve against ProD-driven SJF + quantile
               reservation (Track A).
  --mode real  actually decode the served LM (``--arch``, cut to its first
               ``--n-layers``; random weights from ``--seed`` unless
               ``--ckpt``) on right-padded prompts of random tokens, train
               the ProD head from its own repeated generations, and report
               MAE (Track B). On one TPU v5e:
                 python -m repro.launch.serve --mode real --arch qwen2.5-7b \
                     --n-layers 14 --n-requests 8 --r 4 --max-new 128
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.compile_cache import use_compile_cache
from repro.common.config import PredictorConfig
from repro.configs import get_config, list_archs
from repro.core import bins as bins_mod
from repro.core import targets as targets_mod
from repro.core.metrics import mae, noise_radius
from repro.core.predictor import train_predictor
from repro.data import make_scenario
from repro.models.model_zoo import build_model
from repro.serving.engine import RealEngine, SimEngine
from repro.serving.request import workload_from_scenario
from repro.serving.scheduler import Policy


def run_sim(args):
    data = make_scenario(args.model_tag, args.scenario,
                         n_train=args.n_train, n_test=max(args.n_requests, 200),
                         seed=args.seed)
    bin_max = float(np.quantile(data.len_train, 0.999) * 1.3)
    pcfg = PredictorConfig(n_bins=64, bin_max=bin_max, epochs=args.epochs)
    edges = bins_mod.make_edges(pcfg.n_bins, pcfg.bin_max)
    target = targets_mod.dist_target(jnp.asarray(data.len_train, jnp.float32), edges)
    pred = train_predictor(jax.random.PRNGKey(args.seed),
                           jnp.asarray(data.phi_train["last"]), target, pcfg, edges)
    reqs = workload_from_scenario(data, args.n_requests, seed=args.seed,
                                  arrival_rate=args.arrival_rate)
    print(f"scenario={args.model_tag}/{args.scenario} requests={len(reqs)} "
          f"noise_radius={noise_radius(data.len_test):.1f}")
    rows = []
    for policy in (
        Policy("fcfs", "max", max_seq_len=args.max_seq),
        Policy("fcfs", "quantile", max_seq_len=args.max_seq),
        Policy("sjf_pred", "quantile", max_seq_len=args.max_seq),
        Policy("sjf_oracle", "oracle", max_seq_len=args.max_seq),
    ):
        eng = SimEngine(args.slots, args.kv_budget, policy, predictor=pred)
        st = eng.run(reqs)
        rows.append(st.row())
        print(f"{st.policy:22s} mean_lat={st.mean_latency:9.1f} "
              f"p90={st.p90_latency:9.1f} thr={st.throughput:6.2f} "
              f"waste={st.kv_waste_ratio:.3f} overflow={st.overflow_events}")
    return rows


PROMPT_LEN = (256, 1024)   # real mode: prompt lengths, right-padded to the max


def build_served_model(arch: str, n_layers: int = 0, seed: int = 0):
    """The served LM at its published widths, optionally cut to its first
    ``n_layers`` (the first stage of a pipeline: embedding and output head
    stay), with random weights from ``seed``. Returns (model, params)."""
    cfg = get_config(arch)
    if n_layers:
        cfg = cfg.with_overrides(n_layers=n_layers)
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(seed))


def random_prompts(n: int, min_len: int, max_len: int, vocab: int, seed: int):
    """``n`` prompts of random token ids with lengths uniform in
    [min_len, max_len], right-padded to ``max_len`` (one compiled shape for
    every seed). Returns (prompts (n, max_len) int32, lengths (n,))."""
    rng = np.random.default_rng(seed)
    plens = rng.integers(min_len, max_len + 1, n)
    prompts = rng.integers(0, vocab, (n, max_len)).astype(np.int32)
    prompts[np.arange(max_len)[None, :] >= plens[:, None]] = 0
    return prompts, plens


def fit_length_head(lens: np.ndarray, phi, seed: int = 1, n_bins: int = 32,
                    epochs: int = 40):
    """ProD-D: histogram targets from the repeated generations ``lens``
    (N, r), then the shared head trained on the hidden states ``phi``."""
    pcfg = PredictorConfig(n_bins=n_bins, bin_max=float(lens.max() + 8),
                           epochs=epochs)
    edges = bins_mod.make_edges(pcfg.n_bins, pcfg.bin_max)
    tgt = targets_mod.dist_target(jnp.asarray(lens, jnp.float32), edges)
    return train_predictor(jax.random.PRNGKey(seed), jnp.asarray(phi, jnp.float32),
                           tgt, pcfg, edges)


def run_real(args):
    model, params = build_served_model(args.arch, args.n_layers, args.seed)
    if args.ckpt:
        from repro.training.checkpoint import restore_checkpoint
        params = restore_checkpoint(args.ckpt, {"params": params})["params"]
    eng = RealEngine(model, params, max_new=args.max_new)
    n = args.n_requests
    prompts, plens = random_prompts(n, *PROMPT_LEN, model.cfg.vocab_size,
                                    args.seed)
    lens, phi = eng.repeated_sampling(prompts, plens, r=args.r, seed=args.seed)
    print(f"collected {lens.shape} generations; median lengths "
          f"{np.median(lens, axis=1)[:8]}")
    nr = noise_radius(jnp.asarray(lens))
    split = n // 2
    pred = fit_length_head(lens[:split], phi[:split])
    est = pred.predict(jnp.asarray(phi[split:], jnp.float32))
    true_med = np.median(lens[split:], axis=1)
    print(f"ProD-D on real generations: test MAE {mae(est, jnp.asarray(true_med)):.2f} "
          f"(noise radius {nr:.2f})")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["sim", "real"], default="sim")
    ap.add_argument("--arch", default="tiny-lm", choices=list_archs())
    ap.add_argument("--n-layers", type=int, default=0,
                    help="real mode: keep the first N layers (0 = all)")
    ap.add_argument("--model-tag", default="qwen", choices=["qwen", "llama"])
    ap.add_argument("--scenario", default="chat")
    ap.add_argument("--n-requests", type=int, default=200)
    ap.add_argument("--n-train", type=int, default=800)
    ap.add_argument("--arrival-rate", type=float, default=2.0)
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--kv-budget", type=int, default=40_000)
    ap.add_argument("--max-seq", type=int, default=4096)
    ap.add_argument("--epochs", type=int, default=20)
    ap.add_argument("--max-new", type=int, default=96)
    ap.add_argument("--r", type=int, default=8)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()
    if args.mode == "sim":
        run_sim(args)
    else:
        run_real(args)


if __name__ == "__main__":
    main()
