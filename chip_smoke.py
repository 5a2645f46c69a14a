#!/usr/bin/env python3
"""Bring-up check of the served path on one TPU chip.

Drives the paper's served path once, through the functions that
``repro.launch.serve --mode real`` uses: Qwen2.5-7B at its published widths
(d=3584, 28 heads, 4 KV heads, head_dim 128, d_ff 18944, vocab 152064) cut to
its first 14 of 28 layers — stage one of a two-stage pipeline, with the
embedding and the output head kept so the step stays whole — in bfloat16
with random weights from ``--seed``. Eight right-padded prompts of 256–1024
random tokens get r=4 generations of up to 128 new tokens each
(``RealEngine.repeated_sampling``); the ProD-D head is trained on their
lengths and last-token hidden states φ and queried through the Pallas kernel.

Two agreement checks decide the result:

* decode against prefill — the decode step's logits at a few steps against
  ``prefill(logits_mode="all")`` over the prompt plus the tokens generated so
  far, at the last position;
* head against reference — the fused Pallas head against the ``jax.numpy``
  reference (``impl="xla"``, float32 matmuls at highest precision) on the same
  φ, for the probabilities and the quantiles 0.5, 0.9 and 0.95.

Each phase prints a line; the last line is one JSON object naming the device.
Without a TPU the script exits non-zero before doing anything; any failed
check or phase raises, so it exits non-zero and prints no last line.

    python3 chip_smoke.py [--seed N]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

ARCH, N_LAYERS = "qwen2.5-7b", 14
N_PROMPTS, R, MAX_NEW = 8, 4, 128
LOGIT_STEPS = (0, 1, 31, 127)
QS = (0.5, 0.9, 0.95)
# decode vs prefill, relative L2 error of the logits at one (row, step).
# Both paths run in bfloat16 (8 significant bits, rounding ~2^-9 = 0.2%) and
# round at different points: blocked online-softmax attention over the whole
# sequence vs one query against the cache, matmuls of other shapes. Over 14
# residual layers that compounds to about a percent; 5% leaves room for it,
# while a wrong position, mask or cache slot gives errors of order one.
DECODE_REL_TOL = 5e-2
# head vs reference. Both compute in float32 (the kernel's matmuls and the
# reference's at highest precision); they differ only in summation order over
# d=3584 and in the CDF (triangular matmul vs cumsum): ~1e-6 in probability.
HEAD_PROB_ATOL = 1e-5
# A quantile moves by (bin width / bin mass) per unit of CDF, so 1e-6 in the
# CDF is far below a hundredth of a token unless a bin holds almost no mass.
HEAD_Q_ATOL = 1e-2


class CheckFailed(RuntimeError):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise CheckFailed(what)


def tpu_devices():
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX backend is "
                 f"{devs[0].platform!r}); this check runs only on the chip")
    return devs


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling inside the block."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __enter__(self):
        import jax

        self.seconds, self.compiles = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event, secs, **_):
        if event in self.EVENTS:
            self.seconds += secs
            self.compiles += event == self.EVENTS[-1]


def decode_vs_prefill(eng, prompts, plens, seed, steps=LOGIT_STEPS):
    """Worst relative L2 error, over rows and ``steps``, of the decode-step
    logits against a full prefill of prompt + generated tokens."""
    import jax
    import jax.numpy as jnp

    out = eng.generate(prompts, plens, jax.random.PRNGKey(seed + 1),
                       collect_hidden=False, logit_steps=steps)
    model, max_new = eng.model, eng.max_new
    S = prompts.shape[1] + max_new
    ref_fn = jax.jit(lambda p, b, pos: model.prefill(
        p, b, eng.rt, logits_mode="all")[0][0, pos])
    worst, n = 0.0, 0
    for b in range(prompts.shape[0]):
        # steps at or past this row's EOS decode a frozen position
        live = [s for s in steps if s in out["step_logits"] and s < out["lengths"][b]]
        if not live:
            continue
        pl = int(plens[b])
        seq = np.zeros((1, S), np.int32)
        seq[0, :pl] = prompts[b, :pl]
        seq[0, pl:pl + max_new] = out["tokens"][b]
        batch = {"tokens": jnp.asarray(seq),
                 "attn_valid": jnp.asarray(np.arange(S)[None, :] < pl + max_new)}
        ref = np.asarray(ref_fn(eng.params, batch,
                                jnp.asarray([pl + s for s in live])), np.float32)
        for i, s in enumerate(live):
            got = out["step_logits"][s][b]
            check(bool(np.isfinite(got).all()), f"non-finite decode logits row {b} step {s}")
            err = float(np.linalg.norm(got - ref[i]) / np.linalg.norm(ref[i]))
            worst, n = max(worst, err), n + 1
    check(n > 0, "no live (row, step) pair to compare")
    return worst, n


def head_vs_reference(head, x, impl: str, n_timed: int = 10):
    """Largest differences in probability and in quantile between the fused
    head (jitted, as ``PredictorService`` calls it) and the ``jax.numpy``
    reference at highest matmul precision, and the warm call's milliseconds."""
    import jax

    fused = jax.jit(lambda v: head.quantiles(v, QS, impl=impl))
    pk, qk = jax.block_until_ready(fused(x))
    with jax.default_matmul_precision("highest"):
        px, qx = head.quantiles(x, QS, impl="xla")
    pk, qk, px, qx = (np.asarray(a) for a in (pk, qk, px, qx))
    check(bool(np.isfinite(pk).all() and np.isfinite(qk).all()), "head output not finite")
    t = time.perf_counter()
    for _ in range(n_timed):
        jax.block_until_ready(fused(x))
    head_ms = 1e3 * (time.perf_counter() - t) / n_timed
    return float(np.abs(pk - px).max()), float(np.abs(qk - qx).max()), head_ms


def run(seed: int = 0, arch: str = ARCH, n_layers: int = N_LAYERS,
        n_prompts: int = N_PROMPTS, prompt_len=None, r: int = R,
        max_new: int = MAX_NEW, impl: str = "pallas"):
    """Every phase of the smoke on the current default device. Raises
    :class:`CheckFailed` when a check disagrees."""
    import jax
    import jax.numpy as jnp

    from repro.common.compile_cache import use_compile_cache
    from repro.core.heads import head_init
    from repro.core.predictor import LengthPredictor
    from repro.launch.serve import (PROMPT_LEN, build_served_model, fit_length_head,
                                    random_prompts)
    from repro.serving.engine import RealEngine

    print(f"compile cache: {use_compile_cache()}", flush=True)
    with CompileClock() as clock:
        t = time.perf_counter()
        model, params = build_served_model(arch, n_layers, seed)
        jax.block_until_ready(params)
        cfg = model.cfg
        leaves = jax.tree_util.tree_leaves(params)
        print(f"model: {cfg.name} layers={cfg.n_layers} d={cfg.d_model} "
              f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} "
              f"vocab={cfg.vocab_size} dtype={cfg.dtype} "
              f"params={sum(x.size for x in leaves)} "
              f"param_bytes={sum(x.nbytes for x in leaves)} "
              f"init_s={time.perf_counter() - t:.3f}", flush=True)

        prompts, plens = random_prompts(n_prompts, *(prompt_len or PROMPT_LEN),
                                        cfg.vocab_size, seed)
        eng = RealEngine(model, params, max_new=max_new)
        lens, phi = eng.repeated_sampling(prompts, plens, r=r, seed=seed)
    check(lens.shape == (n_prompts, r) and lens.min() >= 1 and lens.max() <= max_new,
          f"generation lengths out of range: {lens.shape} {lens.min()}..{lens.max()}")
    check(phi.shape == (n_prompts, cfg.d_model)
          and bool(np.isfinite(np.asarray(phi, np.float32)).all()),
          "phi is not finite with shape (B, d)")
    print(f"generate: prompts={n_prompts} prompt_len={plens.min()}..{plens.max()} "
          f"r={r} max_new={max_new} mean_len={lens.mean():.2f} "
          f"saturated_share={(lens == max_new).mean():.3f}", flush=True)
    print(f"compile: {clock.seconds:.3f} s over {clock.compiles} programs "
          "(model init and first generation)", flush=True)
    steady = eng.timings[1:]
    prefill_s = float(np.median([t["prefill_s"] for t in steady]))
    dec_s = sum(t["decode_s"] for t in steady)
    dec_steps = sum(t["decode_steps"] for t in steady)
    print(f"prefill: {prefill_s:.6f} s (B={n_prompts}, S={prompts.shape[1]}, "
          f"median of {len(steady)} warm calls, to block_until_ready)", flush=True)
    print(f"decode: {1e3 * dec_s / dec_steps:.4f} ms per step "
          f"(B={n_prompts}, one token per sequence, the draw included; "
          f"{dec_steps} warm steps)", flush=True)

    worst, n = decode_vs_prefill(eng, prompts, plens, seed)
    print(f"check decode-vs-prefill: max rel L2 {worst:.3e} over {n} (row, step) "
          f"pairs, tol {DECODE_REL_TOL:.0e}", flush=True)
    check(worst <= DECODE_REL_TOL, f"decode disagrees with prefill: {worst:.3e}")

    pred = fit_length_head(lens, phi, seed)
    x = jnp.asarray(phi, jnp.float32)
    # the trained head is nearly one-hot here (every length saturates at
    # max_new), so a freshly drawn head of the same shape, whose mass is
    # spread over many bins, is checked too
    fresh = LengthPredictor(head_init(jax.random.PRNGKey(seed), cfg.d_model,
                                      pred.pcfg.hidden, pred.pcfg.n_bins),
                            pred.edges, pred.pcfg)
    for name, head in (("trained", pred), ("fresh", fresh)):
        dp, dq, head_ms = head_vs_reference(head, x, impl)
        print(f"check head-vs-reference ({name} head): max |dprob| {dp:.3e} "
              f"(tol {HEAD_PROB_ATOL:.0e}), max |dquantile| {dq:.3e} tokens "
              f"(tol {HEAD_Q_ATOL:.0e}) at q={QS}; jitted head call "
              f"{head_ms:.4f} ms at B={n_prompts} ({impl})", flush=True)
        check(dp <= HEAD_PROB_ATOL, f"{name} head probabilities disagree: {dp:.3e}")
        check(dq <= HEAD_Q_ATOL, f"{name} head quantiles disagree: {dq:.3e}")

    stats = jax.devices()[0].memory_stats() or {}
    print(f"memory: peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"bytes_limit={stats.get('bytes_limit')}", flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    devs = tpu_devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind} count={len(devs)}",
          flush=True)
    run(seed=args.seed)
    print(json.dumps({"ok": True, "device": {"platform": d.platform,
                                             "kind": d.device_kind,
                                             "count": len(devs)}}))


if __name__ == "__main__":
    main()
