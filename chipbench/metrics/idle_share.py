"""Share of the traced window (first round's start to last round's end) in
which no operation ran on the device, from the profiler's trace, in %."""


def read(run):
    return run.trace.idle_share if run.trace is not None else None
