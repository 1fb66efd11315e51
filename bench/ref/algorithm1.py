"""Plain reference of the paper's Algorithm 1, vectorised over ends.

Written from the paper (arXiv:1504.00591 §IV) and the monitor settings a
configuration file states; it imports nothing of the system under test.
Per end and per sampling period:

  * a blocked period is discarded (its count never enters the window);
  * the window S holds the last ``window`` valid counts; once it is
    full, every valid period folds one quantile
    ``q = mean(S') + z * std(S')`` (Eq. 3), where S' is S filtered in
    valid mode by the radius-``gauss_radius`` Gaussian of Eq. 2;
  * q-bar is the running (Welford) mean of the q's of the current epoch;
    sigma is the population std of the last ``conv_window`` q-bars, or
    "not ready" (1e30) until that many exist;
  * the last ``conv_window + 2 * log_radius`` sigmas are filtered in
    valid mode by the radius-``log_radius`` Laplacian of Gaussian
    (Eq. 4); the epoch converges when the largest absolute response is
    below ``conv_tol * |q-bar|`` ("rel") or ``conv_tol`` ("abs"), once
    ``min_q_samples`` q's were folded.  Convergence emits q-bar as the
    estimate and resets q-bar and both traces (the window is kept).

Every array operation is cast back to ``dtype``, so the same code is the
float64 reference and, with a lower ``dtype``, the lower-precision control.
Two partial controls lower one part alone: ``eq4_dtype`` computes only
sigma and Eq. 4's test in a lower type, and ``decide`` imposes another
run's convergence decisions, so that the epochs match and only the
estimates carry ``dtype``'s rounding.
The readout is gated as users read it: the last converged q-bar, else
the running q-bar once ``min_q_samples`` q's were folded, else 0.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import numpy as np

__all__ = ["RefResult", "gaussian_taps", "log_taps", "window_quantiles",
           "run_reference", "counting_state"]

NOT_READY = 1e30


def gaussian_taps(radius: int, sigma: float, normalize: bool) -> np.ndarray:
    """Eq. 2: the normal pdf sampled at -radius..radius (unit sum when
    ``normalize``)."""
    x = np.arange(-radius, radius + 1, dtype=np.float64)
    g = np.exp(-x * x / (2.0 * sigma * sigma)) / (math.sqrt(2.0 * math.pi)
                                                 * sigma)
    return g / g.sum() if normalize else g


def log_taps(radius: int, sigma: float) -> np.ndarray:
    """Eq. 4: the second derivative of the normal pdf at -radius..radius,
    (x^2 / s^5 - 1 / s^3) * exp(-x^2 / (2 s^2)) / sqrt(2 pi)."""
    x = np.arange(-radius, radius + 1, dtype=np.float64)
    e = np.exp(-x * x / (2.0 * sigma * sigma)) / math.sqrt(2.0 * math.pi)
    return (x * x / sigma ** 5 - 1.0 / sigma ** 3) * e


class RefResult(NamedTuple):
    epoch: np.ndarray        # (E,) convergences
    last_qbar: np.ndarray    # (E,) last converged q-bar, items/period
    count: np.ndarray        # (E,) q's folded in the current epoch
    mean: np.ndarray         # (E,) running q-bar of the current epoch
    gated: np.ndarray        # (E,) readout, items/period
    conv_periods: list       # per end: periods at which an epoch converged
    conv: np.ndarray         # (N, E) bool: q column j converged the end


def _mean(x, dt, axis=-1):
    n = x.shape[axis]
    return (np.add.reduce(x, axis=axis, dtype=dt) / dt(n)).astype(
        dt, copy=False)


def _std(x, dt, axis=-1):
    mu = np.expand_dims(_mean(x, dt, axis), axis)
    d = (x - mu).astype(dt, copy=False)
    return np.sqrt(_mean((d * d).astype(dt, copy=False), dt, axis)).astype(
        dt, copy=False)


def _compact(counts, blocked):
    """Valid counts of each end in period order, left-aligned: (E, M)
    values (NaN past each end's own count), (E, M) periods, (E,) counts."""
    valid = ~blocked
    order = np.argsort(blocked, axis=0, kind="stable")      # valid first
    n_valid = valid.sum(axis=0)
    m = int(n_valid.max()) if n_valid.size else 0
    order = order[:m]
    vals = np.take_along_axis(counts, order, axis=0).T.astype(np.float64)
    mask = np.arange(m)[None, :] < n_valid[:, None]
    vals[~mask] = np.nan
    return vals, order.T, n_valid


def window_quantiles(vals: np.ndarray, mc: dict, dt=np.float64,
                     block: int = 512) -> np.ndarray:
    """Eq. 2 + Eq. 3 for every full window of each end's valid counts.

    ``vals`` is (E, M) valid counts (NaN padded).  Returns (E, M - w + 1):
    column j is the q of the window ending at valid sample j + w - 1."""
    w = mc["window"]
    g = gaussian_taps(mc["gauss_radius"], mc["gauss_sigma"],
                      mc["gauss_normalize"]).astype(dt, copy=False)
    z = dt(mc["quantile_z"])
    e, m = vals.shape
    out = np.full((e, max(m - w + 1, 0)), np.nan, dt)
    if m < w:
        return out
    n_f = w - len(g) + 1                 # filtered values per window
    n_w = m - w + 1                      # windows
    for lo in range(0, e, block):
        y = vals[lo:lo + block].T.astype(dt)                 # (M, Eb)
        # S' of the window ending at valid sample i is the Gaussian
        # filter of the whole sequence over that window's span
        f = (y[0:m - len(g) + 1] * g[0]).astype(dt, copy=False)
        for k in range(1, len(g)):
            f = (f + (y[k:k + m - len(g) + 1] * g[k]).astype(
                dt, copy=False)).astype(dt, copy=False)
        acc = f[0:n_w].copy()
        for k in range(1, n_f):
            acc = (acc + f[k:k + n_w]).astype(dt, copy=False)
        mu = (acc / dt(n_f)).astype(dt, copy=False)
        acc = np.zeros_like(mu)
        for k in range(n_f):
            d = (f[k:k + n_w] - mu).astype(dt, copy=False)
            acc = (acc + (d * d).astype(dt, copy=False)).astype(dt, copy=False)
        sd = np.sqrt((acc / dt(n_f)).astype(dt, copy=False)).astype(
            dt, copy=False)
        out[lo:lo + block] = (mu + (z * sd).astype(dt, copy=False)).astype(
            dt, copy=False).T
    return out


def run_reference(counts: np.ndarray, blocked: np.ndarray, mc: dict,
                  dt=np.float64, eq4_dtype=None,
                  decide: np.ndarray | None = None) -> RefResult:
    """Algorithm 1 over (T, E) counts and blocked flags from a fresh start.

    ``mc`` holds the monitor settings (the keys of a configuration's
    ``monitor`` block).  ``dt`` is the arithmetic type; ``eq4_dtype``
    (default ``dt``) that of sigma and Eq. 4's test.  ``decide``, the
    ``conv`` of another run over the same counts, replaces the test."""
    if mc["sigma_mode"] != "window_std":
        raise ValueError("the reference implements sigma_mode window_std")
    if mc["conv_tol_mode"] not in ("rel", "abs"):
        raise ValueError(f"bad conv_tol_mode {mc['conv_tol_mode']!r}")
    vals, periods, _ = _compact(np.asarray(counts), np.asarray(blocked))
    q = window_quantiles(vals, mc, dt)                       # (E, N)
    e, n_q = q.shape
    w, cw = mc["window"], mc["conv_window"]
    tl = cw + 2 * mc["log_radius"]
    d4 = eq4_dtype or dt
    lg = log_taps(mc["log_radius"], mc["log_sigma"]).astype(d4)
    if len(lg) != 3:
        raise ValueError("the reference implements log_radius 1")
    n_l = tl - len(lg) + 1
    tol0, rel = d4(mc["conv_tol"]), mc["conv_tol_mode"] == "rel"
    big = d4(NOT_READY)
    c_ = lambda x: x.astype(dt, copy=False)                 # noqa: E731
    c4 = lambda x: x.astype(d4, copy=False)                 # noqa: E731

    cnt = np.zeros(e, np.int64)          # q's folded in this epoch
    mean = np.zeros(e, dt)               # q-bar
    qbars = np.zeros((cw, e), dt)        # last cw q-bars (slot = n % cw)
    s1 = np.full(e, big, d4)             # sigma one fold back
    s2 = np.full(e, big, d4)             # sigma two folds back
    resp = np.full((n_l, e), big, d4)    # last n_l Eq. 4 responses
    epoch = np.zeros(e, np.int64)
    last = np.zeros(e, dt)
    conv = np.zeros((n_q, e), bool)
    active = ~np.isnan(q)
    every = np.arange(e)
    for j in range(n_q):
        rows = every if active[:, j].all() else np.flatnonzero(active[:, j])
        a = slice(None) if rows is every else rows   # a view where it can
        n = cnt[a] + 1
        cnt[a] = n
        m = mean[a]
        m = c_(m + c_(c_(q[a, j] - m) / c_(n)))             # Welford mean
        mean[a] = m
        qbars[(n - 1) % cw, rows] = m
        sig = np.where(n >= cw, _std(c4(qbars[:, a]), d4, 0), big)
        # the newest Eq. 4 response of the sigma trace, centred one back
        r = c4(c4(c4(s2[a] * lg[0]) + c4(s1[a] * lg[1])) + c4(sig * lg[2]))
        s2[a] = s1[a]
        s1[a] = sig
        resp[(n - 1) % n_l, rows] = r
        rmax = np.max(np.abs(resp[:, a]), axis=0)
        tol = c4(tol0 * np.maximum(np.abs(c4(m)), d4(1e-12))) if rel else tol0
        if decide is None:
            hit = ((n >= mc["min_q_samples"]) & (n >= tl)
                   & np.isfinite(rmax) & (rmax < tol))
        else:
            hit = decide[j, rows]
        if hit.any():
            h = rows[hit]
            conv[j, h] = True
            epoch[h] += 1
            last[h] = mean[h]
            cnt[h] = 0                   # resetStats(): the window stays
            mean[h] = 0
            qbars[:, h] = 0
            s1[h] = big
            s2[h] = big
            resp[:, h] = big
    gated = np.where(epoch > 0, last,
                     np.where(cnt >= mc["min_q_samples"], mean, dt(0)))
    # q column j folds valid sample j + w - 1; map it to its period
    conv_periods = [periods[i, np.flatnonzero(conv[:, i]) + w - 1]
                    for i in range(e)]
    f64 = lambda a: np.asarray(a, np.float64)              # noqa: E731
    return RefResult(epoch, f64(last), cnt, f64(mean), f64(gated),
                     conv_periods, conv)


def counting_state(blocked_total: np.ndarray, recent_counts: np.ndarray,
                   recent_blocked: np.ndarray, window: int):
    """What the collector must hand the estimator, counted directly.

    ``blocked_total`` is each end's number of blocked periods;
    ``recent_counts``/``recent_blocked`` are the last K periods (K, E).
    Returns (n_blocked, s_fill, win): the window is each end's last
    ``window`` valid counts, newest last (zeros before the first)."""
    valid = ~recent_blocked
    k, e = valid.shape
    # rank of each valid period counted from the newest (1 = newest)
    rank = np.cumsum(valid[::-1], axis=0)[::-1] * valid
    win = np.zeros((e, window))
    sel = valid & (rank <= window)
    tt, ee = np.nonzero(sel)
    win[ee, window - rank[tt, ee]] = recent_counts[tt, ee]
    n_recent = valid.sum(axis=0)
    return blocked_total, np.minimum(n_recent, window), win
