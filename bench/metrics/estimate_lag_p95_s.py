"""95th percentile, over every rate step of every stepped end in the
window, of the wall time from writing the step's counts to the first
harvested readout from an epoch that began at or after the step (s).
Steps with no such readout by the end of the drain are ``failed``."""

import numpy as np


def read(rec):
    lag = rec.get("lag_s")
    if lag is None or lag.size == 0:
        return None
    return float(np.quantile(lag, 0.95))
