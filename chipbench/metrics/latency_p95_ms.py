"""95th percentile, over every request of the window, of the time from its
send to its reply with tokens and length quantiles in hand (host clock)."""

import numpy as np


def read(run):
    lat = [1e3 * (r["t_reply"] - r["t_send"]) for r in run.rounds
           for _ in range(len(r["lengths"]))]
    return float(np.percentile(lat, 95)) if lat else None
