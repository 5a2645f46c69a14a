"""Process start to the first timed request: JAX start-up, weights drawn,
compilation (or loading it from the cache) and the warm-up round."""


def read(run):
    return run.setup_s
