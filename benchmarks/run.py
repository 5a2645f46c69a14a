"""Benchmark driver — one entry per paper table/figure + system benches.

Prints ``name,value,derived`` CSV rows (spec format). Usage:
    PYTHONPATH=src python -m benchmarks.run [--full] [--only table1,...]
"""

from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale splits (slow; default is CPU-fast)")
    ap.add_argument("--only", default=None,
                    help="comma list: fig1,theory,table1,table23,fig2,serving,online,bins")
    args = ap.parse_args()
    fast = not args.full
    only = set(args.only.split(",")) if args.only else None
    rows = []

    def emit(name, value, derived=""):
        rows.append((name, value, derived))
        print(f"{name},{value},{derived}", flush=True)

    def want(name):
        return only is None or name in only

    t_all = time.time()

    if want("fig1"):
        print("== Figure 1 / A.4: key observations ==", flush=True)
        from benchmarks import bench_fig1
        out = bench_fig1.run()
        checks = bench_fig1.validate(out)
        emit("fig1_calibration_max_rel_err", f"{checks['max_calibration_rel_err']:.3f}",
             "vs paper noise radii")
        emit("fig1_heavy_tails_present", checks["heavy_tails_present"], "")
        sp = bench_fig1.system_prompt_effect()
        emit("fig1_system_prompt_radius_reduction_pct",
             f"{sp['radius_reduction_pct']:.1f}", "A.3 analog")

    if want("theory"):
        print("== Theorem 1 / Lemma 3 ==", flush=True)
        from benchmarks import bench_theory
        out = bench_theory.run()
        checks = bench_theory.validate(out)
        for k, v in checks.items():
            emit(f"theory_{k}", v, "")
        emit("theory_lemma3_worst_ratio",
             f"{max(v['ratio'] for v in out['lemma3'].values()):.3f}", "bound: 2.0")

    t1_rows = None
    if want("table1"):
        print("== Table 1: prompt-only MAE ==", flush=True)
        from benchmarks import bench_table1
        out = bench_table1.run(fast=fast)
        t1_rows = out["rows"]
        for (method, model, _), v in sorted(out["avg"].items()):
            emit(f"table1_avg_{method}_{model}", f"{v:.2f}", "MAE tokens")
        for k, v in out["checks"].items():
            emit(f"table1_{k}", v if not isinstance(v, float) else f"{v:.1f}", "")

    if want("table23"):
        print("== Tables 2-3: single-sample ablation ==", flush=True)
        from benchmarks import bench_table23
        out = bench_table23.run(fast=fast)
        if t1_rows is not None:
            checks = bench_table23.validate(out, t1_rows)
            for k, v in checks.items():
                emit(f"table23_{k}", v if not isinstance(v, float) else f"{v:.1f}", "")

    if want("fig2"):
        print("== Figure 2: budget fairness ==", flush=True)
        from benchmarks import bench_fig2
        out = bench_fig2.run(fast=fast)
        for k, v in bench_fig2.validate(out).items():
            emit(f"fig2_{k}", v, "")

    if want("serving"):
        print("== Serving impact (beyond paper) ==", flush=True)
        from benchmarks import bench_serving
        srows = bench_serving.run(fast=fast)
        for k, v in bench_serving.validate(srows).items():
            emit(f"serving_{k}", v if not isinstance(v, float) else f"{v:.1f}", "")

    if want("online"):
        print("== ProD-O: online remaining-length (beyond paper) ==", flush=True)
        from benchmarks import bench_online
        rep = bench_online.run()
        for k, v in bench_online.validate(rep).items():
            emit(f"online_{k}", v if not isinstance(v, float) else f"{v:.2f}", "")

    if want("bins"):
        print("== Bin-spacing ablation (beyond paper) ==", flush=True)
        from benchmarks import bench_bins_ablation
        out = bench_bins_ablation.run(fast=fast)
        for k, v in bench_bins_ablation.validate(out).items():
            emit(f"bins_{k}", v, "")

    print(f"\ntotal bench time: {time.time()-t_all:.0f}s ({len(rows)} rows)")


if __name__ == "__main__":
    main()
