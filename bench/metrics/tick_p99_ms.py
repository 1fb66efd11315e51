"""99th percentile of the wall time of every ``sample()`` call in the
window, dispatching ticks included (ms)."""

import numpy as np


def read(rec):
    ticks = rec.get("tick_s")
    if ticks is None or ticks.size == 0:
        return None
    return float(np.quantile(ticks, 0.99)) * 1e3
