"""The estimator's share of its roofline (%): the bytes Algorithm 1 must
move per dispatch over the dispatch program's device time at the chip's
peak HBM bandwidth.  The estimator is bound by memory, not arithmetic."""

from harness import roofline


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr["estimator_count"]:
        return None
    per = tr["estimator_s"] / tr["estimator_count"]
    need = roofline.estimator_bytes(rec["ends"], rec["periods_per_dispatch"])
    return 100.0 * need / (per * roofline.peak(rec["device_kind"],
                                               "hbm_bytes_per_s"))
