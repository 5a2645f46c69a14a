"""The decode loop's share of its memory roofline: for each round, the least
time of each decode step t = 1..``decode_steps`` — every matrix weight and
each row's valid K/V at context ``plens + t`` (``chipbench/costs.py``), over
the chip's HBM bytes/s — summed over the rounds, over their summed
``decode_s``, in %. Counted from the traffic's shapes, so it reads the same
work whatever implements the step; None when the rounds lack the counters."""

import numpy as np

from chipbench import costs


def read(run):
    timings = [r.get("timing", {}) for r in run.rounds]
    if not timings or not all("decode_s" in t and "decode_steps" in t for t in timings):
        return None
    least = spent = 0.0
    for r, t in zip(run.rounds, timings):
        n = int(t["decode_steps"])
        context = np.asarray(r["plens"])[None, :] + np.arange(1, n + 1)[:, None]
        nbytes = n * costs.matmul_weight_bytes(run.cfg)
        if n:
            nbytes += costs.decode_attention_cost(run.cfg, context)[1]
        least += nbytes / run.peak["hbm_bytes_per_s"]
        spent += t["decode_s"]
    return 100.0 * least / spent if spent else None
