"""Mean time of the jitted ProD head call per dispatch batch (one per round),
host clock around the call ending in ``block_until_ready``."""


def read(run):
    t = [r["timing"]["head_s"] for r in run.rounds if "head_s" in r["timing"]]
    return 1e3 * sum(t) / len(t) if t else None
