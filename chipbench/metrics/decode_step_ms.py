"""The engine's decode loop per step, host sampling included: total
``decode_s`` over total ``decode_steps`` of the window's rounds."""


def read(run):
    s = sum(r["timing"].get("decode_s", 0.0) for r in run.rounds)
    n = sum(r["timing"].get("decode_steps", 0) for r in run.rounds)
    return 1e3 * s / n if n else None
