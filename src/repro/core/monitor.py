"""Online non-blocking service-rate monitor — the paper's Algorithm 1.

Pipeline (paper §IV):

  tc sample --[discard blocked states]--> sliding window S (size w)
     --[Gaussian filter r=2, Eq.2, valid mode]--> S'
     --[q = mean(S') + 1.64485 * std(S'), Eq.3]--> q stream
     --[Welford running mean]--> q-bar, sigma(q-bar)
     --[LoG filter r=1 sigma=.5, Eq.4 over sigma trace; max|.| < tol]-->
        converged -> emit q-bar, resetStats(), next epoch

Two implementations, same math:

* ``MonitorState`` + ``monitor_update`` — a pure-JAX state machine usable
  under ``jit`` / ``lax.scan`` (and vmappable across thousands of queues;
  the Pallas kernel in ``repro.kernels.monitor`` fuses the window stage).
* ``HostMonitor`` — float64 numpy object used by the real host-side monitor
  threads in ``repro.streams`` (the paper's per-queue monitor thread).

Rates are maintained in *items per period*; callers convert with
``rate = q_bar * d_bytes / T_seconds`` exactly as in the paper.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import filters
from repro.core.backend import resolve_interpret
from repro.core.stats import (Welford, welford_init, welford_update,
                              welford_stderr)

__all__ = [
    "MonitorConfig",
    "MonitorState",
    "MonitorOutput",
    "monitor_init",
    "monitor_update",
    "run_monitor",
    "FleetMonitorState",
    "fleet_monitor_init",
    "run_monitor_fleet",
    "fleet_rate_readout",
    "fleet_dispatch_trace_count",
    "HostMonitor",
    "SamplingPeriodController",
]

Z_95 = 1.64485  # Eq. 3: standard-normal 95th-percentile multiplier.
_BIG = 1e30     # finite "not ready" sentinel (inf would NaN through the LoG)


@dataclasses.dataclass(frozen=True)
class MonitorConfig:
    """Tuning knobs; defaults follow the paper where given."""
    window: int = 32                 # w — sliding window of tc samples
    gauss_radius: int = 2            # paper: radius 2 ("best balance")
    gauss_sigma: float = 1.0
    gauss_normalize: bool = True     # False = verbatim Eq. 2 (sum ~ .9913)
    quantile_z: float = Z_95
    conv_window: int = 16            # paper: w <- 16 for convergence
    log_radius: int = 1              # paper: radius 1
    log_sigma: float = 0.5           # paper: sigma = 1/2
    conv_tol: float = 1e-3           # tolerance on filtered sigma trace
    conv_tol_mode: str = "rel"       # "rel": tol * |q-bar|; "abs": paper's 5e-7
    sigma_mode: str = "window_std"   # "window_std" | "stderr"
    min_q_samples: int = 32          # q obs required before testing conv.

    @classmethod
    def paper_faithful(cls) -> "MonitorConfig":
        """The constants exactly as printed in the paper (abs 5e-7)."""
        return cls(conv_tol=5e-7, conv_tol_mode="abs", gauss_normalize=False)

    @property
    def sig_trace_len(self) -> int:
        return self.conv_window + 2 * self.log_radius

    def __post_init__(self):
        if self.window <= 2 * self.gauss_radius:
            raise ValueError("window must exceed 2*gauss_radius")
        if self.conv_tol_mode not in ("rel", "abs"):
            raise ValueError(f"bad conv_tol_mode {self.conv_tol_mode}")
        if self.sigma_mode not in ("window_std", "stderr"):
            raise ValueError(f"bad sigma_mode {self.sigma_mode}")


class MonitorState(NamedTuple):
    """Per-queue Algorithm-1 state.  All buffers are *index-based circular
    buffers* (write head advances mod length) — a push is a masked O(1)
    write instead of the old shift-everything copy."""
    s_buf: jnp.ndarray       # (window,) circular tc window S
    s_head: jnp.ndarray      # int32, next write slot == oldest entry
    s_fill: jnp.ndarray      # int32, valid entries in s_buf (saturating)
    q_stats: Welford         # running stats of q -> q-bar
    qbar_buf: jnp.ndarray    # (conv_window,) circular recent q-bar values
    qbar_head: jnp.ndarray
    qbar_fill: jnp.ndarray
    sig_buf: jnp.ndarray     # (sig_trace_len,) circular sigma(q-bar) trace
    sig_head: jnp.ndarray
    sig_fill: jnp.ndarray
    epoch: jnp.ndarray       # int32, completed convergences
    last_qbar: jnp.ndarray   # last converged estimate (items/period)
    n_total: jnp.ndarray     # int32 diagnostics
    n_blocked: jnp.ndarray


class MonitorOutput(NamedTuple):
    q: jnp.ndarray           # this step's Eq.3 quantile (0 until window full)
    qbar: jnp.ndarray        # running mean of q
    sigma_qbar: jnp.ndarray  # stability statistic
    converged: jnp.ndarray   # bool — emitted this step
    estimate: jnp.ndarray    # last converged q-bar (items/period)
    epoch: jnp.ndarray


def monitor_init(cfg: MonitorConfig, dtype=jnp.float32) -> MonitorState:
    i0 = jnp.zeros((), jnp.int32)
    f0 = jnp.zeros((), dtype)
    return MonitorState(
        s_buf=jnp.zeros((cfg.window,), dtype),
        s_head=i0,
        s_fill=i0,
        q_stats=welford_init(dtype),
        qbar_buf=jnp.zeros((cfg.conv_window,), dtype),
        qbar_head=i0,
        qbar_fill=i0,
        sig_buf=jnp.zeros((cfg.sig_trace_len,), dtype),
        sig_head=i0,
        sig_fill=i0,
        epoch=i0,
        last_qbar=f0,
        n_total=i0,
        n_blocked=i0,
    )


def _ring_push(buf, head, x, do_push):
    """Masked write of x at the head slot iff do_push; head advances mod n.

    Replaces the old shift-push: no O(w) copy, and the write lowers to one
    masked vector op under vmap across a fleet of queues.
    """
    n = buf.shape[0]
    hit = jnp.logical_and(jnp.arange(n) == head, do_push)
    new = jnp.where(hit, jnp.asarray(x, buf.dtype), buf)
    new_head = jnp.where(do_push, jnp.mod(head + 1, n), head)
    return new, new_head


def _ring_conv(buf, head, taps):
    """Valid-mode correlation of a circular buffer with a static kernel.

    Returns ``(conv, valid)``: the circular correlation (length n, as
    shifted-slice MACs) and the mask of the n-2r windows that do not
    straddle the seam between newest and oldest entry — exactly the
    valid-mode outputs of the chronological window, in rotated order.
    All downstream reductions (mean/std/max|.|) are order-free.
    """
    n = buf.shape[0]
    r = (len(taps) - 1) // 2
    ext = jnp.concatenate([buf, buf[: 2 * r]])
    conv = ext[:n] * jnp.asarray(taps[0], buf.dtype)
    for i in range(1, 2 * r + 1):
        conv = conv + ext[i:i + n] * jnp.asarray(taps[i], buf.dtype)
    valid = jnp.mod(jnp.arange(n) - head, n) < n - 2 * r
    return conv, valid


def _where_tree(cond, new, old):
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(cond, n, o), new, old)


def monitor_update(cfg: MonitorConfig, state: MonitorState, tc, blocked
                   ) -> tuple[MonitorState, MonitorOutput]:
    """One sampling period: ingest (tc, blocked), advance Algorithm 1."""
    dtype = state.s_buf.dtype
    tc = jnp.asarray(tc, dtype)
    blocked = jnp.asarray(blocked, jnp.bool_)
    valid = jnp.logical_not(blocked)

    n_total = state.n_total + 1
    n_blocked = state.n_blocked + blocked.astype(jnp.int32)

    # --- window stage -----------------------------------------------------
    s_buf, s_head = _ring_push(state.s_buf, state.s_head, tc, valid)
    s_fill = jnp.minimum(state.s_fill + valid.astype(jnp.int32), cfg.window)
    window_ready = jnp.logical_and(valid, s_fill >= cfg.window)

    g_taps = filters.gaussian_taps(cfg.gauss_radius, float(cfg.gauss_sigma),
                                   cfg.gauss_normalize)
    conv, conv_ok = _ring_conv(s_buf, s_head, g_taps)
    n_out = cfg.window - 2 * cfg.gauss_radius
    mu_sp = jnp.sum(jnp.where(conv_ok, conv, 0.0)) / n_out
    dev = jnp.where(conv_ok, conv - mu_sp, 0.0)
    sd_sp = jnp.sqrt(jnp.maximum(jnp.sum(dev * dev) / n_out, 0.0))
    q = mu_sp + jnp.asarray(cfg.quantile_z, dtype) * sd_sp  # Eq. 3

    # --- q-bar stage (Welford) --------------------------------------------
    q_stats = _where_tree(window_ready,
                          welford_update(state.q_stats, q), state.q_stats)
    qbar = q_stats.mean

    qbar_buf, qbar_head = _ring_push(state.qbar_buf, state.qbar_head,
                                     qbar, window_ready)
    qbar_fill = jnp.minimum(state.qbar_fill + window_ready.astype(jnp.int32),
                            cfg.conv_window)

    if cfg.sigma_mode == "stderr":
        sigma_qbar = welford_stderr(q_stats)
    else:  # std of the recent q-bar trajectory — its decay *is* stability
        have = qbar_fill >= cfg.conv_window
        sigma_qbar = jnp.where(have, jnp.std(qbar_buf),
                               jnp.asarray(_BIG, dtype))

    sig_buf, sig_head = _ring_push(state.sig_buf, state.sig_head,
                                   sigma_qbar, window_ready)
    sig_fill = jnp.minimum(state.sig_fill + window_ready.astype(jnp.int32),
                           cfg.sig_trace_len)

    # --- convergence stage (Eq. 4) ----------------------------------------
    l_taps = filters.log_taps(cfg.log_radius, float(cfg.log_sigma))
    filt, filt_ok = _ring_conv(sig_buf, sig_head, l_taps)
    resp = jnp.max(jnp.where(filt_ok, jnp.abs(filt), 0.0))
    tol = jnp.asarray(cfg.conv_tol, dtype)
    if cfg.conv_tol_mode == "rel":
        tol = tol * jnp.maximum(jnp.abs(qbar), jnp.asarray(1e-12, dtype))
    trace_ready = jnp.logical_and(sig_fill >= cfg.sig_trace_len,
                                  q_stats.count >= cfg.min_q_samples)
    finite = jnp.isfinite(resp)
    converged = window_ready & trace_ready & finite & (resp < tol)

    # --- emit + resetStats() ----------------------------------------------
    last_qbar = jnp.where(converged, qbar, state.last_qbar)
    epoch = state.epoch + converged.astype(jnp.int32)
    fresh = monitor_init(cfg, dtype)
    q_stats = _where_tree(converged, fresh.q_stats, q_stats)
    qbar_buf = jnp.where(converged, fresh.qbar_buf, qbar_buf)
    qbar_head = jnp.where(converged, fresh.qbar_head, qbar_head)
    qbar_fill = jnp.where(converged, fresh.qbar_fill, qbar_fill)
    sig_buf = jnp.where(converged, fresh.sig_buf, sig_buf)
    sig_head = jnp.where(converged, fresh.sig_head, sig_head)
    sig_fill = jnp.where(converged, fresh.sig_fill, sig_fill)

    new_state = MonitorState(
        s_buf=s_buf, s_head=s_head, s_fill=s_fill, q_stats=q_stats,
        qbar_buf=qbar_buf, qbar_head=qbar_head, qbar_fill=qbar_fill,
        sig_buf=sig_buf, sig_head=sig_head, sig_fill=sig_fill,
        epoch=epoch, last_qbar=last_qbar,
        n_total=n_total, n_blocked=n_blocked)
    out = MonitorOutput(
        q=jnp.where(window_ready, q, jnp.zeros((), dtype)),
        qbar=qbar,
        sigma_qbar=sigma_qbar,
        converged=converged,
        estimate=last_qbar,
        epoch=epoch)
    return new_state, out


def run_monitor(cfg: MonitorConfig, tc_seq, blocked_seq=None,
                dtype=jnp.float32) -> MonitorOutput:
    """Drive the monitor over a whole sample stream with ``lax.scan``.

    Returns stacked ``MonitorOutput`` (leading time axis).  Used by tests,
    benchmarks, and the batched (vmapped) fleet monitor.
    """
    tc_seq = jnp.asarray(tc_seq, dtype)
    if blocked_seq is None:
        blocked_seq = jnp.zeros(tc_seq.shape, jnp.bool_)
    else:
        blocked_seq = jnp.asarray(blocked_seq, jnp.bool_)

    def step(state, xs):
        tc, blk = xs
        return monitor_update(cfg, state, tc, blk)

    _, outs = jax.lax.scan(step, monitor_init(cfg, dtype),
                           (tc_seq, blocked_seq))
    return outs


# ---------------------------------------------------------------------------
# Fleet-scale time-batched monitor (the fused Pallas hot path).
# ---------------------------------------------------------------------------

class FleetMonitorState(NamedTuple):
    """Algorithm-1 state for Q queues at once, laid out for the fused
    (BQ, T) estimators.  Everything is *chronological* (newest entry
    last); there are no ring heads and no saturating fill counters —
    every gate the sequential algorithm expressed through fills is a pure
    function of ``count`` (q-bar fill = min(count, cw), sigma-trace fill
    = min(count, cw+2), response fill = min(count-2, cw)), because all
    three buffers advance on exactly the same fold events.

    The sigma trace is reduced to its two most recent values (the LoG
    stencil has radius 1; older trace entries survive only through the
    response history).  All leaves have leading dim Q; this is the state
    that stays resident in VMEM across a time tile.
    """
    win: jnp.ndarray         # (Q, window) last valid samples, newest last
    s_fill: jnp.ndarray      # (Q,) int32 saturating valid-sample count
    count: jnp.ndarray       # (Q,) Welford n        (float, matches stats)
    mean: jnp.ndarray        # (Q,) Welford mean  == q-bar
    m2: jnp.ndarray          # (Q,) Welford M2
    qhist: jnp.ndarray       # (Q, conv_window) recent q-bar folds
    shist: jnp.ndarray       # (Q, 2) [sigma(t-2), sigma(t-1)]
    rhist: jnp.ndarray       # (Q, conv_window) recent LoG responses
    epoch: jnp.ndarray       # (Q,) int32
    last_qbar: jnp.ndarray   # (Q,) last converged estimate
    n_total: jnp.ndarray     # (Q,) int32
    n_blocked: jnp.ndarray   # (Q,) int32


def fleet_monitor_init(cfg: MonitorConfig, n_queues: int,
                       dtype=jnp.float32) -> FleetMonitorState:
    q = n_queues
    f = lambda *s: jnp.zeros(s, dtype)         # noqa: E731
    i = lambda *s: jnp.zeros(s, jnp.int32)     # noqa: E731
    return FleetMonitorState(
        win=f(q, cfg.window), s_fill=i(q),
        count=f(q), mean=f(q), m2=f(q),
        qhist=f(q, cfg.conv_window), shist=f(q, 2),
        rhist=f(q, cfg.conv_window),
        epoch=i(q), last_qbar=f(q), n_total=i(q), n_blocked=i(q))


_FLEET_TRACE_COUNT = [0]


def fleet_dispatch_trace_count() -> int:
    """How many times the cached fleet-step dispatch has been (re)traced.

    Used by the recompile-count regression tests: ragged fleet sizes must
    map onto one trace per (block_q, chunk_t, config) via queue-axis
    padding, not one trace per Q.
    """
    return _FLEET_TRACE_COUNT[0]


@functools.lru_cache(maxsize=None)
def _fleet_dispatch(cfg: MonitorConfig, impl: str, mode: str,
                    interpret: bool, block_q: int, donate: bool):
    """Jitted fleet step, cached per static configuration.

    The returned function is shape-polymorphic only through jit's own
    shape cache: because ``run_monitor_fleet`` pads the queue axis to a
    ``block_q`` multiple and the time axis to ``chunk_t``, every dispatch
    for a given (block_q, chunk_t, cfg) shares a single trace.  With
    ``donate=True`` the state argument is donated so XLA reuses the fleet
    state buffers in place across dispatches — callers must not touch the
    passed-in state afterwards (the monitoring services never do).
    """
    from repro.kernels.monitor.ops import _fleet_monitor_scan_impl

    def step(state, tc, blocked):
        _FLEET_TRACE_COUNT[0] += 1   # python body runs at trace time only
        return _fleet_monitor_scan_impl(
            cfg, state, tc, blocked, impl=impl, mode=mode,
            interpret=interpret, block_q=block_q)

    return jax.jit(step, donate_argnums=(0,) if donate else ())


def run_monitor_fleet(cfg: MonitorConfig, tc_seq, blocked_seq=None, *,
                      state: FleetMonitorState | None = None,
                      chunk_t: int = 256, impl: str = "rounds",
                      mode: str = "full", interpret: bool | None = None,
                      block_q: int = 256, dtype=jnp.float32,
                      donate: bool = False, pad_q: bool = True
                      ) -> tuple[FleetMonitorState, MonitorOutput | None]:
    """Drive the fused fleet estimator over (Q, T) sample streams.

    Consumes ``chunk_t`` samples per dispatch (instead of one per
    ``lax.scan`` step) and carries ``FleetMonitorState`` across
    dispatches, so arbitrarily long streams run in fixed memory with a
    handful of launches.

    ``impl`` selects the execution path (see ``kernels.monitor.ops``):
    ``"rounds"`` (segmented time-batched XLA form — the CPU fast path),
    ``"pallas"`` (the fused VMEM-resident kernel: compiled on a TPU,
    run by the Pallas interpreter on the CPU backend unless
    ``interpret`` says otherwise — see ``core.backend``) or ``"scan"``
    (pure-jnp sequential oracle).  ``mode="full"`` returns a
    ``MonitorOutput`` whose (Q, T)
    leaves are step-for-step identical to ``jax.vmap(run_monitor)``;
    ``mode="state"`` skips per-step outputs (converged estimates and
    epochs live in the state) and returns ``(state, None)`` — the
    production configuration for large fleets.

    The jitted step is cached per (config, chunk_t, block_q): ``pad_q``
    (default) pads the queue axis up to a ``block_q`` multiple with
    always-blocked rows, so ragged fleet sizes share one trace and one
    executable.  ``donate=True`` donates the state into the dispatch (the
    caller must not reuse the passed-in ``state``) so the (Q,)-leaf fleet
    state updates in place — the monitoring-service hot path.

    Under a ``jax.profiler`` trace the host stages show as spans:
    ``repro.monitor.stage`` (host -> device), ``.pad``, ``.dispatch``
    and ``.unpad`` (see ``kernels/monitor/README.md``, "Tracing").
    """
    annotate = jax.profiler.TraceAnnotation
    with annotate("repro.monitor.stage"):    # host -> device transfer
        tc_seq = jnp.asarray(tc_seq, dtype)
        if tc_seq.ndim != 2:
            raise ValueError(f"tc_seq must be (Q, T), got {tc_seq.shape}")
        Q, T = tc_seq.shape
        if blocked_seq is not None:
            blocked_seq = jnp.asarray(blocked_seq, jnp.bool_)
        if state is None:
            state = fleet_monitor_init(cfg, Q, dtype)

    rpad = (-(-Q // block_q) * block_q - Q) if pad_q else 0
    if rpad:                      # padded rows are permanently blocked
        with annotate("repro.monitor.pad"):
            if blocked_seq is None:
                blocked_seq = jnp.zeros((Q, T), jnp.bool_)
            tc_seq = jnp.pad(tc_seq, ((0, rpad), (0, 0)))
            blocked_seq = jnp.pad(blocked_seq, ((0, rpad), (0, 0)),
                                  constant_values=True)
            state = jax.tree_util.tree_map(
                lambda a: jnp.pad(a, ((0, rpad),)
                                  + ((0, 0),) * (a.ndim - 1)), state)

    with annotate("repro.monitor.dispatch"):
        step = _fleet_dispatch(cfg, impl, mode,
                               resolve_interpret(interpret), block_q, donate)
        outs = []
        for t0 in range(0, T, chunk_t):
            tc_c = tc_seq[:, t0:t0 + chunk_t]
            blk_c = (None if blocked_seq is None
                     else blocked_seq[:, t0:t0 + chunk_t])
            pad = chunk_t - tc_c.shape[1]
            if pad:                        # pad the tail chunk as blocked
                if blk_c is None:
                    blk_c = jnp.zeros(tc_c.shape, jnp.bool_)
                tc_c = jnp.pad(tc_c, ((0, 0), (0, pad)))
                blk_c = jnp.pad(blk_c, ((0, 0), (0, pad)),
                                constant_values=True)
            state, out = step(state, tc_c, blk_c)
            if pad:                        # padded steps are not real
                state = state._replace(n_total=state.n_total - pad,
                                       n_blocked=state.n_blocked - pad)
            outs.append(out)
    with annotate("repro.monitor.unpad"):
        if rpad:
            state = jax.tree_util.tree_map(lambda a: a[:Q], state)
        if mode != "full":
            return state, None
        merged = MonitorOutput(*(jnp.concatenate(parts, axis=1)[:Q, :T]
                                 for parts in zip(*outs)))
    return state, merged


def gated_rate_arrays(cfg: MonitorConfig, epoch, count, mean, last,
                      period_s: float = 1.0) -> np.ndarray:
    """The readiness-gate formula on bare arrays: the last converged
    q-bar, else the running q-bar once ``min_q_samples`` folds
    accumulated, else 0 — one definition shared by the state readout
    below and the monitoring service's harvest-time mirrors, so the
    advisory and control-loop sense paths cannot drift."""
    est = np.where(np.asarray(epoch) > 0, np.asarray(last),
                   np.where(np.asarray(count) >= cfg.min_q_samples,
                            np.asarray(mean), 0.0))
    return est / period_s if period_s > 0 else np.zeros_like(est)


def fleet_rate_readout(cfg: MonitorConfig, state: FleetMonitorState,
                       period_s: float = 1.0) -> np.ndarray:
    """Per-queue service-rate readout (items/s) with the Welford-count
    readiness gate.

    A queue that has converged at least once reports its last converged
    q-bar.  Before the first convergence the running q-bar is reported
    only once the current epoch has accumulated ``min_q_samples`` folds —
    never a raw partial-window sample, which is exactly the noise the
    paper's Algorithm 1 exists to filter out.  Unready queues report 0.
    """
    return gated_rate_arrays(cfg, state.epoch, state.count, state.mean,
                             state.last_qbar, period_s)


# ---------------------------------------------------------------------------
# Host-side implementation (the paper's monitor thread), float64 numpy.
# ---------------------------------------------------------------------------

class HostMonitor:
    """Per-queue online monitor for the host pipeline threads.

    Same algorithm as ``monitor_update`` in float64; kept dependency-light
    (numpy only) because it runs on the instrumentation thread and must obey
    the paper's low-overhead contract (1-2%).
    """

    def __init__(self, cfg: MonitorConfig | None = None, *,
                 period_s: float = 1e-3, item_bytes: float = 1.0):
        self.cfg = cfg or MonitorConfig()
        self.period_s = float(period_s)
        self.item_bytes = float(item_bytes)
        c = self.cfg
        self._gauss = filters.gaussian_kernel(
            c.gauss_radius, c.gauss_sigma, normalize=c.gauss_normalize)
        self._log = filters.log_kernel(c.log_radius, c.log_sigma)
        self.n_total = 0
        self.n_blocked = 0
        self.epoch = 0
        self.last_qbar = 0.0
        self.estimates: list[float] = []   # converged q-bar per epoch
        # Double-write ring: each sample is stored at p and p+w, so the
        # chronological window is always the contiguous view
        # _s[p+1 : p+1+w] — an O(1) push (two stores) instead of the old
        # O(w) shift, on the instrumentation thread where the paper's
        # 1-2% overhead budget applies.
        self._s = np.zeros(2 * c.window)
        self._s_head = c.window - 1
        self._s_fill = 0
        self._reset_stats()

    # -- Algorithm 1's resetStats() ----------------------------------------
    def _reset_stats(self):
        c = self.cfg
        self._n = 0
        self._mean = 0.0
        self._m2 = 0.0
        self._qbars = collections.deque(maxlen=c.conv_window)
        self._sigs = collections.deque(maxlen=c.sig_trace_len)

    def update(self, tc: float, blocked: bool = False) -> bool:
        """Ingest one period's sample; returns True if converged+emitted."""
        c = self.cfg
        self.n_total += 1
        if blocked:
            self.n_blocked += 1
            return False
        w = c.window
        p = (self._s_head + 1) % w
        self._s_head = p
        self._s[p] = tc
        self._s[p + w] = tc
        self._s_fill = min(self._s_fill + 1, w)
        if self._s_fill < w:
            return False

        sp = filters.convolve_valid(self._s[p + 1:p + 1 + w], self._gauss)
        q = float(np.mean(sp) + c.quantile_z * np.std(sp))

        self._n += 1
        delta = q - self._mean
        self._mean += delta / self._n
        self._m2 += delta * (q - self._mean)
        qbar = self._mean

        self._qbars.append(qbar)      # deque: O(1), evicts the oldest
        if c.sigma_mode == "stderr":
            sig = math.sqrt(self._m2 / self._n / self._n) if self._n else 0.0
        else:
            sig = (float(np.std(self._qbars))
                   if len(self._qbars) >= c.conv_window else _BIG)
        self._sigs.append(sig)

        if (len(self._sigs) < c.sig_trace_len
                or self._n < c.min_q_samples):
            return False
        filt = filters.convolve_valid(np.asarray(self._sigs), self._log)
        resp = float(np.max(np.abs(filt)))
        if not math.isfinite(resp):
            return False
        tol = c.conv_tol * (max(abs(qbar), 1e-12)
                            if c.conv_tol_mode == "rel" else 1.0)
        if resp >= tol:
            return False

        self.last_qbar = qbar
        self.estimates.append(qbar)
        self.epoch += 1
        self._reset_stats()
        return True

    # -- readouts ------------------------------------------------------------
    @property
    def qbar(self) -> float:
        return self._mean if self._n else self.last_qbar

    def rate_items_per_s(self) -> float:
        q = self.last_qbar if self.epoch else self.qbar
        return q / self.period_s if self.period_s > 0 else 0.0

    def rate_bytes_per_s(self) -> float:
        return self.rate_items_per_s() * self.item_bytes

    def observed_blocking_fraction(self) -> float:
        return self.n_blocked / self.n_total if self.n_total else 0.0


# ---------------------------------------------------------------------------
# Sampling-period determination (paper §IV-A).
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SamplingPeriodController:
    """Find the widest stable sampling period T (paper Fig. 6).

    Start at the timing mechanism's minimum latency and lengthen T while
    (1) no blockage occurred at either queue end in the last ``k`` periods
    and (2) the realized period stayed within ``eps`` of target for the last
    ``j`` periods.  If T cannot stabilize at the minimum, the method *fails
    knowingly* (``failed`` is set) — the paper's stated behavior.
    """
    base_latency_s: float = 300e-9     # paper: ~50-300 ns timer latency
    max_period_s: float = 10e-3        # ~ scheduler quantum
    k_no_block: int = 8
    j_stable: int = 8
    eps_rel: float = 0.25
    growth: float = 2.0

    def __post_init__(self):
        self.period_s = self.base_latency_s
        self._no_block_run = 0
        self._stable_run = 0
        self._unstable_run = 0
        self.failed = False

    def observe(self, realized_period_s: float, blocked: bool) -> float:
        """Report one period's outcome; returns the (possibly new) T."""
        stable = (abs(realized_period_s - self.period_s)
                  <= self.eps_rel * self.period_s)
        self._stable_run = self._stable_run + 1 if stable else 0
        self._unstable_run = 0 if stable else self._unstable_run + 1
        self._no_block_run = 0 if blocked else self._no_block_run + 1

        if (self._no_block_run >= self.k_no_block
                and self._stable_run >= self.j_stable
                and self.period_s * self.growth <= self.max_period_s):
            self.period_s *= self.growth
            self._no_block_run = 0
            self._stable_run = 0
        elif self._unstable_run >= self.j_stable:
            if self.period_s <= self.base_latency_s * 1.0001:
                self.failed = True     # cannot stabilize even at minimum
            else:
                self.period_s = max(self.period_s / self.growth,
                                    self.base_latency_s)
            self._unstable_run = 0
        return self.period_s
