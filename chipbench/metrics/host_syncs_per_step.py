"""Device→host reads the engine's decode loop makes per decode step, as the
engine counts them (``RealEngine.timings["host_syncs"]``): total over total
``decode_steps`` of the window's rounds. None where the engine keeps no such
count."""


def read(run):
    if not run.rounds or not all("host_syncs" in r["timing"] for r in run.rounds):
        return None
    n = sum(r["timing"].get("decode_steps", 0) for r in run.rounds)
    return sum(r["timing"]["host_syncs"] for r in run.rounds) / n if n else None
