"""Median wall time of a tick that dispatches: staging, the transpose,
the dispatch of the estimator and the harvest one dispatch behind (ms)."""

import numpy as np


def read(rec):
    ticks = rec.get("tick_s")
    if ticks is None:
        return None
    busy = ticks[rec["dispatching"]]
    return float(np.median(busy)) * 1e3 if busy.size else None
