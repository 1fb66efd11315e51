"""Public ops: fleet-scale batched monitor.

``fleet_monitor_scan`` is the throughput path: it consumes a (Q, T) tile
of raw (tc, blocked) samples per dispatch, discards blocked samples by
stream compaction, runs the fused Pallas Algorithm-1 scan (Stage A window
estimates + Stage B convergence fold, all fleet state VMEM-resident), and
scatters the per-valid-step outputs back onto the original timeline so the
result is step-for-step identical to ``jax.vmap(run_monitor)``.

``fleet_monitor_q`` / ``fleet_monitor_step`` remain the one-tick forms for
callers that hand-maintain windows; ``fleet_monitor_step`` now honors
``MonitorConfig.sigma_mode`` so fleet and single-queue paths converge
identically.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.monitor import _BIG, FleetMonitorState, MonitorConfig, \
    MonitorOutput
from repro.core.stats import Welford, welford_stderr, welford_update
from repro.kernels.monitor.kernel import (batched_monitor_pallas,
                                          monitor_fleet_pallas)
from repro.kernels.monitor.ref import (batched_monitor_ref, fleet_sigma,
                                       monitor_fleet_ref)
from repro.kernels.monitor.rounds import monitor_fleet_rounds

__all__ = ["fleet_monitor_q", "fleet_monitor_step", "fleet_monitor_scan",
           "FleetStepState", "fleet_step_init", "batched_monitor_ref"]


# ---------------------------------------------------------------------------
# Fused (Q, T) scan.
# ---------------------------------------------------------------------------

def _pack_state(state: FleetMonitorState):
    """Per-queue scalars as the kernel's lane-major (8, Q) row blocks."""
    z_f = jnp.zeros_like(state.count)
    z_i = jnp.zeros_like(state.s_fill)
    fstate = jnp.stack([state.count, state.mean, state.m2,
                        state.last_qbar, z_f, z_f, z_f, z_f], axis=0)
    istate = jnp.stack([state.s_fill, state.epoch, z_i, z_i, z_i, z_i,
                        z_i, z_i], axis=0)
    return fstate, istate


def _carry_to_state(carry, win, n_total, n_blocked) -> FleetMonitorState:
    (s_fill, count, mean, m2, qhist, shist, rhist, epoch, last_qbar) = carry
    return FleetMonitorState(
        win=win, s_fill=s_fill, count=count, mean=mean, m2=m2,
        qhist=qhist, shist=shist, rhist=rhist,
        epoch=epoch, last_qbar=last_qbar,
        n_total=n_total, n_blocked=n_blocked)


def _entry_sigma(cfg: MonitorConfig, state: FleetMonitorState):
    """sigma(q-bar) implied by the carried state (pre-tile value)."""
    return fleet_sigma(state.count, state.m2, state.qhist,
                       window_std=cfg.sigma_mode == "window_std",
                       cw=cfg.conv_window)


_DEAD = 1 << 30     # shift-code flag of a blocked (or emptied) slot


def _shift_left(a, b: int, fill):
    """out[:, j] = a[:, j + b], ``fill`` past the right edge: one pad op,
    which XLA fuses into its consumer, where a slice is copied first."""
    return jax.lax.pad(a, jnp.asarray(fill, a.dtype),
                       ((0, 0, 0), (-b, b, 0)))


def _compact(tc, blocked):
    """Stream compaction: drop blocked samples, keep time order.

    Returns (comp, m, cnt): compacted samples (the tail [m, T) zeroed),
    per-queue valid counts, and the per-step running valid count used to
    map results back.

    No scatter, sort or gather (a TPU v5e lowers a per-row scatter to a
    sort of every destination key): a valid sample at column j moves
    left by d = j + 1 - cnt, the blocked samples before it, in a ladder
    of shifted selects over d's bits, low bit first.  d never decreases
    along a row's valid samples, so positions stay strictly increasing
    after every stage and no two samples meet.
    """
    Q, T = tc.shape
    if blocked is None:
        cnt = jnp.broadcast_to(jnp.arange(1, T + 1)[None, :], (Q, T))
        return tc, jnp.full((Q,), T, jnp.int32), cnt
    valid = jnp.logical_not(blocked)
    cnt = jnp.cumsum(valid.astype(jnp.int32), axis=1)       # (Q, T)
    m = cnt[:, -1]
    d = jnp.arange(1, T + 1, dtype=jnp.int32)[None, :] - cnt
    code = jnp.where(valid, d, _DEAD)     # remaining shift, or dead
    x = tc
    for k in range((T - 1).bit_length()):
        b = 1 << k
        src = _shift_left(code, b, _DEAD)
        arrive = (src & (b | _DEAD)) == b       # live, shift has bit b
        stays = (code & (b | _DEAD)) == 0
        x = jnp.where(arrive, _shift_left(x, b, 0), x)
        code = jnp.where(arrive, src, jnp.where(stays, code, _DEAD))
    comp = jnp.where(code < _DEAD, x, jnp.zeros((), tc.dtype))
    return comp, m, cnt


def _fleet_monitor_scan_impl(cfg: MonitorConfig, state: FleetMonitorState,
                             tc, blocked=None, *, impl: str = "rounds",
                             mode: str = "full",
                             interpret: bool | None = None,
                             block_q: int = 256, sub_t: int = 32):
    """One fused dispatch over a (Q, T) tile.

    impl: "rounds" (segmented time-batched XLA form — host fast path),
    "pallas" (fused VMEM-resident kernel — the TPU contract) or "scan"
    (pure-jnp sequential oracle).  mode="full" returns a MonitorOutput
    with (Q, T) leaves matching ``monitor_update`` step for step;
    mode="state" skips per-step outputs and returns (new_state, None).
    """
    tc = jnp.asarray(tc, jnp.float32)
    Q, T = tc.shape
    W = cfg.window
    with jax.named_scope("monitor.compact"):
        comp, m, cnt = _compact(tc, blocked)

    # --- fused scan over the compacted tile -----------------------------
    full = mode == "full"
    q_c = None
    if impl == "pallas":
        # the kernel is lane-major (queues on lanes): transpose (Q, k)
        # leaves in and out, and pad the lane axis to a block multiple
        BQ = block_q
        Qp = -(-Q // BQ) * BQ
        lanes = lambda a: jnp.pad(a, ((0, 0), (0, Qp - Q)))  # noqa: E731
        with jax.named_scope("monitor.layout"):
            fstate, istate = _pack_state(state)
            ins = [lanes(a) for a in (comp.T, m[None, :], state.win.T,
                                      fstate, istate, state.qhist.T,
                                      state.shist.T, state.rhist.T)]
        with jax.named_scope("monitor.pallas"):
            outs = monitor_fleet_pallas(cfg, *ins, block_q=BQ,
                                        interpret=interpret)
        with jax.named_scope("monitor.layout"):
            (q_c, qbar_c, sig_c, conv_c, est_c, ep_c,
             fout, iout, qhist, shist, rhist) = [o[:, :Q].T for o in outs]
            carry = (iout[:, 0], fout[:, 0], fout[:, 1], fout[:, 2],
                     qhist, shist, rhist, iout[:, 1], fout[:, 3])
    elif impl == "scan":
        carry, (q_c, qbar_c, sig_c, conv_c, est_c, ep_c) = \
            monitor_fleet_ref(cfg, state, comp, m)
    elif impl == "rounds":
        carry, outs = monitor_fleet_rounds(cfg, state, comp, m,
                                           mode=mode, sub_t=sub_t)
        if full:
            (q_c, qbar_c, sig_c, conv_c, est_c, ep_c) = outs
    else:
        raise ValueError(f"unknown impl {impl!r}")

    # --- window carry: last W valid samples per queue -------------------
    with jax.named_scope("monitor.carry"):
        if impl == "rounds":   # rounds maintains the window itself
            carry, win = carry[:9], carry[9]
        else:
            ext = jnp.concatenate([state.win, comp], axis=1)  # (Q, W+T)
            idx = m[:, None] + jnp.arange(W)[None, :]
            win = jnp.take_along_axis(ext, idx, axis=1)

        n_total = state.n_total + T
        n_blocked = state.n_blocked + (
            jnp.zeros((Q,), jnp.int32) if blocked is None
            else jnp.sum(blocked, axis=1, dtype=jnp.int32))
    new_state = _carry_to_state(carry, win, n_total, n_blocked)

    if not full:
        return new_state, None

    if blocked is None:    # compact timeline == original timeline
        return new_state, MonitorOutput(
            q=q_c, qbar=qbar_c, sigma_qbar=sig_c,
            converged=conv_c.astype(jnp.bool_), estimate=est_c,
            epoch=ep_c)

    # --- scatter back onto the original (possibly blocked) timeline ----
    valid = jnp.logical_not(blocked)
    g_idx = jnp.clip(cnt - 1, 0, T - 1)
    gat = lambda a: jnp.take_along_axis(a, g_idx, axis=1)   # noqa: E731
    has = cnt >= 1
    hold = lambda a, e: jnp.where(has, gat(a), e[:, None])  # noqa: E731
    # a blocked step after a converged step must replay the *post-reset*
    # statistics (monitor_update recomputes them from the reset state):
    # q-bar resets to 0, sigma to the not-ready sentinel (window_std) or
    # the empty-stats stderr of 0
    g_conv = gat(conv_c).astype(jnp.bool_)
    sig_reset = _BIG if cfg.sigma_mode == "window_std" else 0.0
    post = lambda a, r: jnp.where(g_conv, jnp.asarray(r, a.dtype),  # noqa: E731
                                  gat(a))
    out = MonitorOutput(
        q=jnp.where(valid, gat(q_c), 0.0),
        qbar=jnp.where(
            valid, gat(qbar_c),
            jnp.where(has, post(qbar_c, 0.0), state.mean[:, None])),
        sigma_qbar=jnp.where(
            valid, gat(sig_c),
            jnp.where(has, post(sig_c, sig_reset),
                      _entry_sigma(cfg, state)[:, None])),
        converged=jnp.where(valid, g_conv, False),
        estimate=hold(est_c, state.last_qbar),
        epoch=hold(ep_c, state.epoch),
    )
    return new_state, out


# The public jitted form.  ``run_monitor_fleet`` does NOT call this one:
# it builds its own cached dispatch from ``_fleet_monitor_scan_impl`` with
# the queue axis padded to a ``block_q`` multiple (so ragged fleets share
# one trace) and optional state donation (so fleet state buffers are
# reused in place across dispatches).
fleet_monitor_scan = functools.partial(
    jax.jit, static_argnames=("cfg", "impl", "mode", "interpret",
                              "block_q", "sub_t"))(_fleet_monitor_scan_impl)


# ---------------------------------------------------------------------------
# One-tick forms.
# ---------------------------------------------------------------------------

def fleet_monitor_q(windows, *, use_pallas: bool = True,
                    interpret: bool | None = None, block_q: int = 256):
    """(Q, w) windows -> (Q,) Eq.3 quantile estimates."""
    if use_pallas:
        q, _, _ = batched_monitor_pallas(windows, interpret=interpret,
                                         block_q=block_q)
        return q
    q, _, _ = batched_monitor_ref(windows)
    return q


class FleetStepState(NamedTuple):
    """Per-tick fleet stats state: vector Welford + the q-bar ring that
    ``sigma_mode='window_std'`` needs (leaves shaped (Q,) / (Q, cw))."""
    welford: Welford
    qbar_ring: jnp.ndarray
    qbar_head: jnp.ndarray
    qbar_fill: jnp.ndarray


def fleet_step_init(cfg: MonitorConfig, n_queues: int,
                    dtype=jnp.float32) -> FleetStepState:
    z = jnp.zeros((n_queues,), dtype)
    return FleetStepState(
        welford=Welford(count=z, mean=z, m2=z),
        qbar_ring=jnp.zeros((n_queues, cfg.conv_window), dtype),
        qbar_head=jnp.zeros((n_queues,), jnp.int32),
        qbar_fill=jnp.zeros((n_queues,), jnp.int32))


def fleet_monitor_step(windows, state, *, cfg: Optional[MonitorConfig] = None,
                       use_pallas: bool = True,
                       interpret: bool | None = None):
    """One fleet monitoring tick: (Q, w) windows + per-queue stats state
    -> ``(q, new_state, sigma_qbar)``.

    ``state`` may be a :class:`FleetStepState` or a bare vector
    :class:`Welford` (legacy form; implies ``sigma_mode='stderr'`` since
    a Welford state alone cannot express the window-std trajectory).
    sigma(q-bar) follows ``cfg.sigma_mode`` — the same statistic the
    single-queue ``monitor_update`` uses — instead of a hard-coded
    stderr formula.
    """
    cfg = cfg or MonitorConfig()
    q = fleet_monitor_q(windows, use_pallas=use_pallas,
                        interpret=interpret)
    bare = isinstance(state, Welford)
    wf = state if bare else state.welford
    new_wf = jax.vmap(welford_update)(wf, q)
    if bare:
        return q, new_wf, welford_stderr(new_wf)

    if cfg.sigma_mode == "stderr":
        sigma = welford_stderr(new_wf)
        new_state = state._replace(welford=new_wf)
        return q, new_state, sigma

    cw = state.qbar_ring.shape[1]
    qbar = new_wf.mean
    lane = jnp.arange(cw)[None, :]
    ring = jnp.where(lane == state.qbar_head[:, None], qbar[:, None],
                     state.qbar_ring)
    head = jnp.mod(state.qbar_head + 1, cw)
    fill = jnp.minimum(state.qbar_fill + 1, cw)
    sigma = fleet_sigma(fill, new_wf.m2, ring, window_std=True, cw=cw)
    new_state = FleetStepState(welford=new_wf, qbar_ring=ring,
                               qbar_head=head, qbar_fill=fill)
    return q, new_state, sigma
