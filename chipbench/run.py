#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints progress and the compared numbers with their limits on standard
error, and as the last line of standard output one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones), ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``. Exits non-zero, printing no result, when
JAX finds no TPU or fewer chips than the cell asks for: it never falls back to
the CPU.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None):
    args = parse(argv)
    from chipbench import bench

    chips = bench.find_cell(bench.load_json(ROOT / "BENCHMARK.json"),
                            args.workload)["chips"]
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        sys.exit(f"chipbench: needs {chips} TPU chip(s); JAX found "
                 f"{len(devs)} {devs[0].platform!r} device(s)")
    result = bench.run_cell(args.workload, args.seed, args.seconds,
                            bool(args.trace), t0=T0)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
