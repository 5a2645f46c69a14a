"""Mean time of the engine's prefill call over the window's rounds, as the
engine records it (``RealEngine.timings["prefill_s"]``, host clock ending in
``block_until_ready``)."""


def read(run):
    t = [r["timing"]["prefill_s"] for r in run.rounds if "prefill_s" in r["timing"]]
    return 1e3 * sum(t) / len(t) if t else None
