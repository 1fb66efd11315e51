"""Shared math + pure-jnp oracles for the batched monitor kernels.

Three levels:

* ``batched_monitor_ref`` — the original per-tick window stage (Eq. 2+3)
  for (Q, w) windows.
* ``fleet_window_stage`` / ``fleet_step`` — the *time-batched* form of
  Algorithm 1 over a (Q, T) tile of compacted samples.  The Pallas
  kernel in ``kernel.py`` executes exactly these functions on
  VMEM-resident blocks, and ``monitor_fleet_ref`` drives them as a pure
  ``lax.scan`` — kernel and oracle share one implementation of the math
  and differ only in memory movement.
* ``rounds.py`` builds the segmented, fully time-vectorized CPU fast
  path on the same static parameters and window stage.

The time-batched window stage is the big algorithmic lever: the
Gaussian stencil is applied once per *sample* (5 MACs) instead of once
per *window position*, and each step's mean/std come from sliding sums
built as a static shifted-slice doubling ladder — O(log w) vector ops
for the whole tile instead of O(w) per step.
"""

from __future__ import annotations

import types

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.filters import gaussian_kernel, log_kernel
from repro.core.monitor import _BIG, MonitorConfig, Z_95

__all__ = ["batched_monitor_ref", "monitor_fleet_ref",
           "fleet_static_params", "fleet_window_stage", "fleet_step",
           "fleet_sigma", "carry_of_state", "slide_sum_valid",
           "slide_max_valid"]


def fleet_sigma(count, m2, qhist, *, window_std: bool, cw: int,
                axis: int = 1):
    """The fleet paths' sigma(q-bar), one definition for all of them.

    window_std: masked std of the last ``cw`` q-bar folds, gated on
    ``count >= cw`` with the not-ready ``_BIG`` sentinel otherwise.
    Else the Welford stderr sqrt(m2 / count^2) with empty-stats guard
    (matches ``stats.welford_stderr``).  ``axis`` is the history axis of
    ``qhist`` (see ``fleet_step``).
    """
    if window_std:
        muq = jnp.mean(qhist, axis=axis, keepdims=True)
        dq = qhist - muq
        sig = jnp.sqrt(jnp.mean(dq * dq, axis=axis, keepdims=axis == 0))
        return jnp.where(count >= cw, sig, jnp.asarray(_BIG, sig.dtype))
    safe = jnp.where(count > 0, count, 1.0)
    var = jnp.where(count > 0, m2 / safe, 0.0)
    return jnp.sqrt(jnp.maximum(var / safe, 0.0))


def batched_monitor_ref(windows, *, radius: int = 2, sigma: float = 1.0,
                        z: float = Z_95):
    """windows: (Q, w) -> (q, mu, sd) each (Q,) float32."""
    w = jnp.asarray(windows, jnp.float32)
    taps = np.asarray(gaussian_kernel(radius, sigma, normalize=True),
                      np.float32)
    n_out = w.shape[-1] - (2 * radius)
    acc = jnp.zeros(w.shape[:-1] + (n_out,), jnp.float32)
    for i in range(2 * radius + 1):
        acc = acc + w[..., i:i + n_out] * taps[i]
    mu = jnp.mean(acc, axis=-1)
    sd = jnp.std(acc, axis=-1)
    return mu + jnp.float32(z) * sd, mu, sd


# ---------------------------------------------------------------------------
# Static parameters + sliding-window ladders.
# ---------------------------------------------------------------------------

def fleet_static_params(cfg: MonitorConfig) -> types.SimpleNamespace:
    """Bake the config into hashable python scalars for the kernels."""
    g = gaussian_kernel(cfg.gauss_radius, cfg.gauss_sigma,
                        normalize=cfg.gauss_normalize)
    log3 = log_kernel(cfg.log_radius, cfg.log_sigma)
    if len(log3) != 3:
        raise NotImplementedError(
            "fused fleet scan supports log_radius=1 (3-tap LoG) only")
    sl = cfg.sig_trace_len
    return types.SimpleNamespace(
        window=cfg.window,
        gauss_taps=tuple(float(t) for t in g),
        gauss_radius=cfg.gauss_radius,
        z=float(cfg.quantile_z),
        conv_window=cfg.conv_window,
        log_taps=tuple(float(t) for t in log3),
        conv_tol=float(cfg.conv_tol),
        rel_tol=cfg.conv_tol_mode == "rel",
        window_std=cfg.sigma_mode == "window_std",
        min_q=float(cfg.min_q_samples),
        # a fresh epoch needs >= gap folds before it can converge, which
        # statically bounds convergences per tile (rounds.py relies on it)
        gap=max(sl, int(cfg.min_q_samples)),
    )


def _sl(x, start, stop, axis):
    return jax.lax.slice_in_dim(x, start, stop, axis=axis)


def _ladder(x, n, combine, axis):
    """Valid-mode sliding reduce of width n along ``axis``, built as a
    static shifted-slice doubling ladder (no pads, no gathers — fuses
    well under XLA and lowers on TPU)."""
    L = x.shape[axis]
    n_out = L - n + 1
    pows = {1: x}
    k = 1
    while k * 2 <= n:
        s = pows[k]
        m = s.shape[axis]
        pows[k * 2] = combine(_sl(s, 0, m - k, axis), _sl(s, k, m, axis))
        k *= 2
    acc = None
    off = 0
    for k in sorted(pows, reverse=True):
        if n & k:
            part = _sl(pows[k], off, off + n_out, axis)
            acc = part if acc is None else combine(acc, part)
            off += k
    return acc


def slide_sum_valid(x, n, axis=-1):
    return _ladder(x, n, jnp.add, axis % x.ndim)


def slide_max_valid(x, n, axis=-1):
    return _ladder(x, n, jnp.maximum, axis % x.ndim)


# ---------------------------------------------------------------------------
# Stage A: time-batched window estimates.
# ---------------------------------------------------------------------------

def fleet_window_stage(P, win, comp, axis: int = 1):
    """Time-batched Eq. 2+3 over a compacted tile.

    win: (B, w) carried window (newest last); comp: (B, T) compacted
    valid samples.  Returns q: (B, T) — the Eq. 3 quantile after each
    compacted sample (garbage until the window is full; callers gate on
    readiness).  ``axis`` is the time axis: 1 as above, 0 for the
    transposed (w, B) / (T, B) layout of the Pallas kernel.
    """
    W, r, n = P.window, P.gauss_radius, P.window - 2 * P.gauss_radius
    T = comp.shape[axis]
    ext = jnp.concatenate([win, comp], axis=axis)        # (B, W+T)
    L = W + T - 2 * r
    conv = _sl(ext, 0, L, axis) * P.gauss_taps[0]
    for i in range(1, 2 * r + 1):
        conv = conv + _sl(ext, i, i + L, axis) * P.gauss_taps[i]  # (B, L)
    # center first: the windowed sums then cancel at ~machine eps in f32
    c = jnp.mean(conv, axis=axis, keepdims=True)
    d = conv - c
    s1 = slide_sum_valid(d, n, axis)                     # (B, T+1)
    s2 = slide_sum_valid(d * d, n, axis)
    # step t's window ends at ext col W+t -> sum windows start at t+1
    mu = _sl(s1, 1, T + 1, axis) / n
    var = _sl(s2, 1, T + 1, axis) / n - mu * mu
    sd = jnp.sqrt(jnp.maximum(var, 0.0))
    return mu + c + P.z * sd


# ---------------------------------------------------------------------------
# Stage B, sequential form (the Pallas kernel's inner loop + oracle).
# ---------------------------------------------------------------------------

def carry_of_state(state) -> tuple:
    """FleetMonitorState -> Stage-B carry tuple (drops win/n_* leaves)."""
    return (state.s_fill, state.count, state.mean, state.m2,
            state.qhist, state.shist, state.rhist,
            state.epoch, state.last_qbar)


def fleet_step(P, carry, q_t, t, m, axis: int = 1):
    """One Stage-B step: fold one compacted sample's q for every queue.

    All carries are per-queue vectors or chronological histories; every
    update is a masked vector op with no data-dependent control flow.
    ``axis`` is the history axis: 1 for (B, k) histories with (B,)
    per-queue vectors (the scan oracle), 0 for (k, B) histories with
    (1, B) per-queue rows (the Pallas kernel: queues on lanes, history
    on sublanes).  Returns (new_carry, outputs) with outputs a 6-tuple of
    per-queue vectors in ``MonitorOutput`` order.
    """
    (s_fill, count, mean, m2, qhist, shist, rhist, epoch, last_qbar) = carry
    W, CW = P.window, P.conv_window
    SL = CW + 2

    def col(v):       # a per-queue vector, broadcastable over a history
        return v[:, None] if axis == 1 else v

    def entry(h, i):  # history entry i as a per-queue vector
        return h[:, i] if axis == 1 else h[i:i + 1]

    def push(h, v):   # chronological shift-push of a per-queue vector
        return jnp.concatenate([_sl(h, 1, h.shape[axis], axis), col(v)],
                               axis=axis)

    valid = t < m
    s_fill = jnp.minimum(s_fill + valid.astype(jnp.int32), W)
    ready = jnp.logical_and(valid, s_fill >= W)
    rc = col(ready)

    # Welford fold (identical op order to stats.welford_update)
    cnt1 = count + 1.0
    delta = q_t - mean
    mean1 = mean + delta / cnt1
    m21 = m2 + delta * (q_t - mean1)
    count = jnp.where(ready, cnt1, count)
    mean = jnp.where(ready, mean1, mean)
    m2 = jnp.where(ready, m21, m2)
    qbar = mean

    # chronological shift-push (fills are functions of count, see state)
    qhist = jnp.where(rc, push(qhist, qbar), qhist)
    sig = fleet_sigma(count, m2, qhist, window_std=P.window_std, cw=CW,
                      axis=axis)

    # LoG response over the chronological (t-2, t-1, t) sigma stencil; a
    # response enters the history only once all three taps are post-reset
    l0, l1, l2 = P.log_taps
    resp_new = l0 * entry(shist, 0) + l1 * entry(shist, 1) + l2 * sig
    rpush = jnp.logical_and(ready, count >= 3)
    rhist = jnp.where(col(rpush), push(rhist, resp_new), rhist)
    shist = jnp.where(rc, push(shist, sig), shist)

    # convergence test (Eq. 4): count >= SL <=> CW responses post-reset
    resp = jnp.max(jnp.abs(rhist), axis=axis, keepdims=axis == 0)
    trace_ready = count >= max(SL, P.min_q)
    tol = jnp.asarray(P.conv_tol, qbar.dtype)
    if P.rel_tol:
        tol = tol * jnp.maximum(jnp.abs(qbar), 1e-12)
    conv = ready & trace_ready & jnp.isfinite(resp) & (resp < tol)

    # emit + resetStats() (histories need no clearing: every read is
    # gated on count, which only re-arms after a full overwrite)
    last_qbar = jnp.where(conv, qbar, last_qbar)
    epoch = epoch + conv.astype(jnp.int32)
    count = jnp.where(conv, 0.0, count)
    mean = jnp.where(conv, 0.0, mean)
    m2 = jnp.where(conv, 0.0, m2)

    new_carry = (s_fill, count, mean, m2, qhist, shist, rhist,
                 epoch, last_qbar)
    outs = (jnp.where(ready, q_t, 0.0), qbar, sig, conv, last_qbar, epoch)
    return new_carry, outs


def monitor_fleet_ref(cfg: MonitorConfig, state, comp, m):
    """Pure-jnp fused fleet scan over a compacted (Q, T) tile.

    Same math as the Pallas kernel (literally the same stage functions),
    expressed as one ``lax.scan``.  Returns (new_carry, cols) with cols
    a 6-tuple of (Q, T) output planes.
    """
    P = fleet_static_params(cfg)
    q_seq = fleet_window_stage(P, state.win, comp)

    def step(carry, xs):
        t, q_t = xs
        return fleet_step(P, carry, q_t, t, m)

    T = comp.shape[1]
    carry, outs = jax.lax.scan(
        step, carry_of_state(state), (jnp.arange(T), q_seq.T))
    return carry, tuple(o.T for o in outs)
