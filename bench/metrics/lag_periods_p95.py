"""The steps of ``estimate_lag_p95_s`` counted in periods fed between
the step and its fresh readout: the estimator's convergence plus the
harvest delay, free of the host clock."""

import numpy as np


def read(rec):
    lag = rec.get("lag_periods")
    if lag is None or lag.size == 0:
        return None
    return float(np.quantile(lag, 0.95))
