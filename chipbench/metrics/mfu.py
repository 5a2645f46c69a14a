"""Model FLOPs of the work delivered in the window — every request's prefill
over its real prompt tokens and the decode steps that yielded its output
tokens, counted from the configuration's shapes — over the window's seconds
times the chip's bf16 peak, in %."""

from chipbench import costs


def read(run):
    if not run.rounds:
        return None
    flops = sum(costs.request_flops(run.cfg, int(P), int(n))
                for r in run.rounds for P, n in zip(r["plens"], r["lengths"]))
    return 100.0 * flops / (run.rounds[-1]["t_reply"] * run.peak["bf16_flops_per_s"])
