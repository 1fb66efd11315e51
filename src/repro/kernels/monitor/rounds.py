"""Segmented time-batched fleet scan — the host-side (XLA) fast path.

The sequential Stage B in ``ref.py`` pays one XLA op dispatch per sample
per statistic; on CPU that floor dominates.  This module removes the
per-sample loop entirely by exploiting a structural property of
Algorithm 1: after ``resetStats()`` a fresh epoch needs at least
``gap = max(sig_trace_len, min_q_samples)`` folds before it can converge
again, so a tile of ``sub_t <= gap`` steps contains at most one
convergence event per queue — a *statically bounded* number of
"segment evaluations" with no data-dependent control flow.

Dispatch-scope precompute (tiling-invariant): stream compaction, the
time-batched window stage (the Gaussian stencil hits each *sample* once
instead of each window position), the fold-readiness mask, and prefix
sums of the centered q stream.  Each sub-tile then runs one vectorized
*detection* evaluation — q-bar in closed form from prefix sums,
sigma(q-bar) via a width-cw sliding ladder over the q-bar timeline, the
LoG trace from shifted slices, the Eq. 4 response from a sliding-max
ladder, first convergence by argmax — and one *carry* evaluation that
rebuilds the post-reset tail statistics and harvests the chronological
histories the next tile needs.  Histories are the same (Q, cw) buffers
the sequential form keeps, so all implementations share
``FleetMonitorState``.

Everything is shifted-slice ladders and two gather-free per-row
selections — ``row_shift`` (an offset run of columns, a ladder of
selects over the offset's bits) and ``row_pick`` (one column, a masked
row max) — because a TPU v5e runs per-row gathers element by element.
No scatters (the compaction in ``ops.py`` is a select ladder too), no
cumsum primitives, no per-sample control flow.  The precompute, each
detection and each carry evaluation run under ``jax.named_scope``
(``monitor.window`` / ``monitor.detect`` / ``monitor.carry``), so a
profiler trace names the phase of every op.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.monitor import _BIG, MonitorConfig
from repro.kernels.monitor.ref import (fleet_static_params,
                                       fleet_window_stage, slide_max_valid,
                                       slide_sum_valid)

__all__ = ["monitor_fleet_rounds"]


def _prefix(x):
    """Inclusive prefix sums via a doubling ladder, with a leading zero
    column: returns (Q, L+1) with out[:, j] = sum(x[:, :j])."""
    L = x.shape[1]
    k = 1
    while k < L:
        x = x + jnp.pad(x, ((0, 0), (k, 0)))[:, :L]
        k *= 2
    return jnp.pad(x, ((0, 0), (1, 0)))


def row_shift(x, s, n: int, smax: int):
    """out[:, j] = x[:, min(s + j, C - 1)] for j < n: a per-row offset
    s in [0, smax] (Q, 1) into the (Q, C) plane, then n columns — what
    ``take_along_axis`` with clipped indices returns, built as a binary
    ladder of selects over the bits of s (no gather)."""
    bits = [1 << k for k in range(smax.bit_length())]
    pad = n + sum(bits) - x.shape[1]
    if pad > 0:            # edge padding keeps the clip at column C-1
        x = jnp.pad(x, ((0, 0), (0, pad)), mode="edge")
    for b in reversed(bits):
        w = n + b - 1
        x = jnp.where((s & b) != 0, x[:, b:b + w], x[:, :w])
    return x[:, :n]


def row_pick(x, i):
    """out[:, 0] = x[:, clip(i, 0, C - 1)] for a (Q, 1) column index i:
    a masked max over the row (no gather).  Exact for every value,
    signed zeros and infinities included."""
    col = jnp.arange(x.shape[1])[None, :]
    hit = col == jnp.clip(i, 0, x.shape[1] - 1)
    return jnp.max(jnp.where(hit, x, -jnp.inf), axis=1, keepdims=True)


def monitor_fleet_rounds(cfg: MonitorConfig, state, comp, m, *,
                         mode: str = "full", sub_t: int = 32):
    """Run the segmented fleet scan over a compacted (Q, T) tile.

    comp: (Q, T) compacted valid samples, m: (Q,) valid counts.  Returns
    ``(carry, outs)``: carry is the 9-leaf Stage-B tuple plus the window
    carry appended (10 leaves); outs is a 6-tuple of (Q, T) compact-time
    output planes, or None when mode != "full".
    """
    P = fleet_static_params(cfg)
    Q, T = comp.shape
    W, CW = P.window, P.conv_window
    gap = P.gap
    l0, l1, l2 = P.log_taps
    f32 = comp.dtype
    big = jnp.asarray(_BIG, f32)

    count, mean, m2 = state.count, state.mean, state.m2
    qhist, shist, rhist = state.qhist, state.shist, state.rhist
    epoch, last = state.epoch, state.last_qbar

    # ---- dispatch-scope precompute (tiling-invariant) ----
    with jax.named_scope("monitor.window"):
        q = fleet_window_stage(P, state.win, comp)           # (Q, T)
        mc_g = m[:, None]
        F0 = jnp.maximum(W - 1 - state.s_fill, 0)[:, None]   # first fold
        tt_g = jnp.arange(T)[None, :]
        ready_g = (tt_g < mc_g) & (tt_g >= F0)
        nready = jnp.maximum(jnp.sum(ready_g, 1, keepdims=True), 1)
        cq = jnp.sum(jnp.where(ready_g, q, 0.0), 1,
                     keepdims=True) / nready
        dq = jnp.where(ready_g, q - cq, 0.0)
        ps1 = _prefix(dq)                                    # (Q, T+1)
        ps2 = _prefix(dq * dq)

    a = jnp.zeros((Q,), jnp.int32)     # current segment start, global col
    out_cols = [] if mode == "full" else None

    def segment_planes(c0, L, A, count, mean):
        """Closed-form per-step statistics of the current segments over
        tile cols [c0, c0+L): q-bar, sigma timeline pieces, LoG trace."""
        tt = tt_g[:, c0:c0 + L]
        k = jnp.clip(tt - A + 1, 0, T).astype(f32)
        have = k > 0
        cnt = count[:, None] + k
        csafe = jnp.maximum(cnt, 1.0)
        S1 = ps1[:, c0 + 1:c0 + L + 1] - row_pick(ps1, A)
        qbar = jnp.where(
            have, mean[:, None] + (S1 + k * (cq - mean[:, None])) / csafe,
            mean[:, None])
        tl = jnp.concatenate([qhist, qbar], axis=1)          # (Q, CW+L)
        if P.window_std:
            Dt = tl - cq
            s1w = slide_sum_valid(Dt, CW)                    # (Q, L+1)
            s2w = slide_sum_valid(Dt * Dt, CW)
            muw = s1w / CW
            stdw = jnp.sqrt(jnp.maximum(s2w / CW - muw * muw, 0.0))
            sig_in = jnp.where(cnt >= CW, stdw[:, 1:], big)
            e0 = jnp.where(count >= CW, stdw[:, 0], big)
        else:
            S2 = ps2[:, c0 + 1:c0 + L + 1] - row_pick(ps2, A)
            ksafe = jnp.maximum(k, 1.0)
            mb = S1 / ksafe + cq
            m2b = jnp.maximum(S2 - (S1 * S1) / ksafe, 0.0)
            dlt = mb - mean[:, None]
            m2t = jnp.where(have, m2[:, None] + m2b
                            + dlt * dlt * count[:, None] * k / csafe,
                            m2[:, None])
            s0 = jnp.where(count > 0, count, 1.0)
            e0 = jnp.sqrt(jnp.maximum(
                jnp.where(count > 0, m2 / s0, 0.0) / s0, 0.0))
            sig_in = jnp.where(
                have, jnp.sqrt(jnp.maximum(m2t / csafe / csafe, 0.0)),
                e0[:, None])
        stl = jnp.concatenate([shist, sig_in], axis=1)       # (Q, 2+L)
        log_in = (l0 * stl[:, :L] + l1 * stl[:, 1:L + 1]
                  + l2 * stl[:, 2:])
        ltl = jnp.concatenate([rhist, log_in], axis=1)       # (Q, CW+L)
        return tt, k, have, cnt, qbar, tl, stl, ltl, sig_in, e0

    for c0 in range(0, T, sub_t):
        L = min(sub_t, T - c0)
        n_detect = 1 + (L - 1) // gap    # 1 for any sub_t <= gap

        for e in range(n_detect):
            with jax.named_scope("monitor.detect"):
                A = jnp.maximum(a[:, None], F0)
                (tt, k, have, cnt, qbar, tl, stl, ltl, sig_in, e0) = \
                    segment_planes(c0, L, A, count, mean)
                resp_in = slide_max_valid(jnp.abs(ltl), CW)[:, 1:]
                tol = jnp.asarray(P.conv_tol, f32)
                if P.rel_tol:
                    tol = tol * jnp.maximum(jnp.abs(qbar), 1e-12)
                convp = (have & (tt < mc_g) & (cnt >= float(gap))
                         & jnp.isfinite(resp_in) & (resp_in < tol))
                exists = jnp.any(convp, 1)
                j1 = jnp.argmax(convp, 1) + c0                   # global col
                t1 = jnp.where(exists, j1, T)
                qlast = row_pick(qbar, (t1 - c0)[:, None])[:, 0]

                if mode == "full":
                    tl_loc = tt - c0
                    span = (tt >= jnp.maximum(a[:, None] - c0, 0) + c0) \
                        & (tt <= jnp.minimum(t1, c0 + L - 1)[:, None])
                    at1 = (tt == t1[:, None]) & exists[:, None]
                    sig_step = jnp.where(have, sig_in, e0[:, None])
                    if e == 0:
                        oq = jnp.where(span, qbar, 0.0)
                        osg = jnp.where(span, sig_step, 0.0)
                        ocv = at1 & span
                        oes = jnp.where(span, jnp.where(
                            at1, qlast[:, None], last[:, None]), 0.0)
                        oep = jnp.where(span, epoch[:, None]
                                        + at1.astype(jnp.int32), 0)
                    else:
                        oq = jnp.where(span, qbar, oq)
                        osg = jnp.where(span, sig_step, osg)
                        ocv = ocv | (at1 & span)
                        oes = jnp.where(span, jnp.where(
                            at1, qlast[:, None], last[:, None]), oes)
                        oep = jnp.where(span, epoch[:, None]
                                        + at1.astype(jnp.int32), oep)

                zf = jnp.zeros_like(count)
                a = jnp.where(exists, (t1 + 1).astype(jnp.int32), a)
                count = jnp.where(exists, zf, count)
                mean = jnp.where(exists, zf, mean)
                m2 = jnp.where(exists, zf, m2)
                epoch = epoch + exists.astype(jnp.int32)
                last = jnp.where(exists, qlast, last)

        # ---- carry evaluation: no detection (the gap bound rules out a
        # further convergence in this tile); rebuilds the post-reset tail
        # and harvests the chronological histories ----
        with jax.named_scope("monitor.carry"):
            A = jnp.maximum(a[:, None], F0)
            (tt, k, have, cnt, qbar, tl, stl, ltl, sig_in, e0) = \
                segment_planes(c0, L, A, count, mean)
            if mode == "full":
                span = tt >= a[:, None]
                sig_step = jnp.where(have, sig_in, e0[:, None])
                oq = jnp.where(span, qbar, oq)
                osg = jnp.where(span, sig_step, osg)
                oes = jnp.where(span, last[:, None], oes)
                oep = jnp.where(span, epoch[:, None], oep)
                out_cols.append((jnp.where(ready_g[:, c0:c0 + L],
                                           q[:, c0:c0 + L], 0.0),
                                 oq, osg, ocv, oes, oep))

            # Welford carry: absorb this tile's folds of the live segment
            # [A, absorb_end) into (count, mean, m2) — closed form + Chan
            absorb = jnp.minimum(mc_g, c0 + L)                   # (Q, 1)
            kend = jnp.clip(absorb - A, 0, T).astype(f32)
            havek = kend[:, 0] > 0
            countF = count + kend[:, 0]
            S1e = row_pick(ps1, absorb) - row_pick(ps1, A)
            S2e = row_pick(ps2, absorb) - row_pick(ps2, A)
            ke = jnp.maximum(kend, 1.0)
            mbe = S1e / ke + cq
            m2be = jnp.maximum(S2e - S1e * S1e / ke, 0.0)
            de = mbe - mean[:, None]
            meanF = jnp.where(
                havek,
                (mean[:, None] + (S1e + kend * (cq - mean[:, None]))
                 / jnp.maximum(count[:, None] + kend, 1.0))[:, 0], mean)
            m2F = jnp.where(
                havek, (m2[:, None] + m2be + de * de * count[:, None] * kend
                        / jnp.maximum(count[:, None] + kend, 1.0))[:, 0], m2)
            count, mean, m2 = countF, meanF, m2F
            # the absorbed folds must not be re-counted by the next tile
            a = jnp.maximum(a, absorb[:, 0].astype(jnp.int32))

            m_l = jnp.clip(m - c0, 0, L)[:, None]
            qhist = row_shift(tl, m_l, CW, L)
            shist = row_shift(stl, m_l, 2, L)
            rhist = row_shift(ltl, m_l, CW, L)

    # ---- dispatch-level carries ----
    with jax.named_scope("monitor.carry"):
        ext = jnp.concatenate([state.win, comp], axis=1)
        win = row_shift(ext, m[:, None], W, T)
        s_fill = jnp.minimum(state.s_fill + m, W)

    carry = (s_fill, count, mean, m2, qhist, shist, rhist, epoch, last,
             win)
    if mode != "full":
        return carry, None
    outs = tuple(jnp.concatenate(parts, axis=1)
                 for parts in zip(*out_cols))
    return carry, outs
