"""Peak device memory in use over the run, as the runtime counts it
(``memory_stats()["peak_bytes_in_use"]``), over the memory it may use
(``bytes_limit``), in %."""


def read(run):
    peak, limit = run.memory.get("peak_bytes_in_use"), run.memory.get("bytes_limit")
    return 100.0 * peak / limit if peak and limit else None
