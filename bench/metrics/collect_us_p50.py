"""Median wall time of a collector tick that does not dispatch: the
copy-and-zero of every end into the staging row (us)."""

import numpy as np


def read(rec):
    ticks = rec.get("tick_s")
    if ticks is None:
        return None
    quiet = ticks[~rec["dispatching"]]
    return float(np.median(quiet)) * 1e6 if quiet.size else None
