"""Output tokens delivered in the window, over the time from the window's
start to the last reply (host clock)."""


def read(run):
    if not run.rounds:
        return None
    tokens = sum(int(r["lengths"].sum()) for r in run.rounds)
    return tokens / run.rounds[-1]["t_reply"]
