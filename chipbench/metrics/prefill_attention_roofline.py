"""Share of its roofline reached by prefill attention: causal attention over
each request's real prompt tokens, at the chip's peaks, over the device time
of the instructions under ``fusedkernel_flash_attention`` in the trace, in %."""

from chipbench import costs

SCOPE = "fusedkernel_flash_attention"


def read(run):
    t = run.trace.scope_s.get(SCOPE) if run.trace is not None else None
    if not t:
        return None
    flops = nbytes = 0.0
    for r in run.rounds:
        f, b = costs.prefill_attention_cost(run.cfg, r["plens"])
        flops, nbytes = flops + f, nbytes + b
    return costs.roofline_share(flops, nbytes, t, run.peak)
