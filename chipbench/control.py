#!/usr/bin/env python3
"""Readings of the program and of its control, for setting a cell's limits.

    python3 chipbench/control.py --workload <cell> --seconds <s> --seeds 11 12 13

For each seed, in one process: a run of the cell (set-up, a short window at
the cell's own load, the comparison) that also puts the reference in the
program's place in float8 and reads it the same way. Prints one JSON line
per seed: the program's numbers and the control's. The benchmark's own runs
never run this; ``PERF.md`` records what it read and the limits set from it.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--seeds", nargs="+", type=int, required=True)
    args = ap.parse_args(argv)
    import jax

    from chipbench import bench

    if jax.devices()[0].platform != "tpu":
        sys.exit("chipbench control: no TPU found")
    for seed in args.seeds:
        res = bench.run_cell(args.workload, seed, args.seconds, False,
                             t0=time.perf_counter(), controls=("fp8",))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": res["correct"], "failed": res["failed"],
                          "program": {k: v["value"] for k, v in res["checks"].items()},
                          "controls": res["controls"], "checked": res["checked"],
                          "metrics": res["metrics"]}), flush=True)


if __name__ == "__main__":
    main()
