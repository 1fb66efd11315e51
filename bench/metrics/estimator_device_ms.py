"""Device time of the estimator's dispatch program per dispatch, from
the profiler's trace (ms)."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr["estimator_count"]:
        return None
    return tr["estimator_s"] / tr["estimator_count"] * 1e3
