"""How ``correct`` is decided: the served path against the reference.

Two kinds of number, each with its limit from the configuration file:

* exact, over every end: the collector, staging, transpose, padding and
  chunking must hand the estimator exactly the counts that were written.
  ``count_mismatch`` counts ends whose folded-period or blocked-period
  count differs from the traffic; ``window_mismatch`` counts ends whose
  last ``window`` valid counts differ.  Both limits are 0.
* Algorithm 1, over the ends the reference covers (every stepped end and
  a seeded sample): ``split_share`` is the share whose number of
  converged epochs differs from the float64 reference's,
  ``rate_gap_p50`` the median relative gap between the served readout
  and the reference's, and ``rate_gap_max`` the largest such gap over
  the ends whose current epoch also began where the reference's did
  (same epoch count, same q's folded since), so that a wrong readout
  on a few ends shows.  A float32 estimator splits from the reference
  at a small share of ends, where Eq. 4's test sits at its threshold;
  the limits are set between what correct runs read and what the same
  reference computed one precision lower reads.
"""

from __future__ import annotations

import math
import sys

import numpy as np

from ref.algorithm1 import counting_state, run_reference

__all__ = ["covered_ends", "exact_numbers", "alg1_numbers", "reference",
           "controls", "verdict", "report"]


def covered_ends(stepped: np.ndarray, n_ends: int, n_cover: int,
                 seed: int) -> np.ndarray:
    """The ends the reference follows: every stepped end, and the rest
    of ``n_cover`` drawn from the seed."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 7]))
    others = np.setdiff1d(np.arange(n_ends), stepped)
    n_other = min(max(n_cover - stepped.size, 0), others.size)
    return np.sort(np.concatenate(
        [stepped, rng.choice(others, n_other, replace=False)]))


def exact_numbers(prog: dict, t_fed: int, rec, window: int) -> dict:
    """``prog`` holds public-order arrays n_total, n_blocked, s_fill, win
    read from the program; ``rec`` is the ``traffic.Recorded`` cycle."""
    k = 4 * window
    while True:
        cnt, blk = rec.recent(t_fed, min(k, t_fed))
        if k >= t_fed or (~blk).sum(axis=0).min() >= window:
            break
        k *= 2
    n_blocked, s_fill, win = counting_state(rec.blocked_totals(t_fed),
                                            cnt, blk, window)
    counts_off = ((np.asarray(prog["n_total"]) != t_fed)
                  | (np.asarray(prog["n_blocked"]) != n_blocked))
    win_off = ((np.asarray(prog["s_fill"]) != s_fill)
               | np.any(np.asarray(prog["win"], np.float64) != win, axis=1))
    return {"count_mismatch": int(counts_off.sum()),
            "window_mismatch": int(win_off.sum())}


def reference(mc: dict, rec, ends, t_fed: int, dt=np.float64, **kw):
    cnt, blk = rec.rows(ends, 0, t_fed)
    return run_reference(cnt, blk, mc, dt, **kw)


def alg1_numbers(epoch, count, gated, ref) -> dict:
    """``epoch``, ``count`` (q's folded in the current epoch) and
    ``gated`` (items/period) at the covered ends, against the float64
    reference's.  With no end in step with the reference the largest gap
    is NaN, which no limit passes."""
    epoch = np.asarray(epoch)
    gated = np.asarray(gated, np.float64)
    gap = np.abs(gated - ref.gated) / np.maximum(np.abs(ref.gated), 1.0)
    same = (epoch == ref.epoch) & (np.asarray(count) == ref.count)
    return {"split_share": float(np.mean(epoch != ref.epoch)),
            "rate_gap_p50": float(np.median(gap)),
            "rate_gap_max": float(gap[same].max()) if same.any()
            else math.nan}


def controls(mc: dict, rec, ends, t_fed: int, ref) -> dict:
    """The reference one precision lower (bfloat16) in the program's
    place, whole and in two parts: Eq. 4's test alone (``bf16_eq4``) and
    the estimates alone under the float64 run's decisions
    (``bf16_estimates``).  Each is compared as the program is."""
    import ml_dtypes
    bf16 = ml_dtypes.bfloat16
    runs = {"bf16": reference(mc, rec, ends, t_fed, bf16),
            "bf16_eq4": reference(mc, rec, ends, t_fed, eq4_dtype=bf16),
            "bf16_estimates": reference(mc, rec, ends, t_fed, bf16,
                                        decide=ref.conv)}
    return {k: alg1_numbers(r.epoch, r.count, r.gated, ref)
            for k, r in runs.items()}


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    checks = {}
    ok = True
    for name, value in numbers.items():
        limit = limits[name]
        good = (value is not None and not math.isnan(value)
                and value <= limit)
        ok &= bool(good)
        checks[name] = {"value": value, "limit": limit}
    return ok, checks


def report(checks: dict) -> None:
    """The numbers compared, each beside its limit, as the last lines
    of standard error."""
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
