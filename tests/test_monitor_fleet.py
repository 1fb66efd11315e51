"""Parity tests for the fused time-batched fleet monitor.

Every implementation (segmented rounds, sequential jnp scan, Pallas
kernel in interpret mode) must reproduce the float64 ``HostMonitor``
oracle and the per-sample ``run_monitor`` path on identical streams —
including convergence-reset epochs and blocked-sample discards.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.monitor import (HostMonitor, MonitorConfig,
                                fleet_monitor_init, run_monitor,
                                run_monitor_fleet)
from repro.core.simulate import (TandemConfig, sample_periods_fleet,
                                 simulate_tandem)
from repro.kernels.monitor.ops import fleet_monitor_scan

IMPLS = ["rounds", "scan", "pallas"]


def _noisy_streams(Q=5, T=700, seed=0, p_block=0.06):
    rng = np.random.default_rng(seed)
    base = rng.uniform(100, 400, (Q, 1))
    tc = rng.poisson(base, (Q, T)).astype(np.float64)
    blocked = rng.random((Q, T)) < p_block
    return tc, blocked


def _host_epochs(cfg, tc, blocked):
    """Drive the float64 HostMonitor oracle; returns epochs+estimates."""
    epochs, ests = [], []
    for q in range(tc.shape[0]):
        hm = HostMonitor(cfg)
        per_epoch = []
        for t, b in zip(tc[q], blocked[q]):
            if hm.update(float(t), bool(b)):
                per_epoch.append(hm.estimates[-1])
        epochs.append(hm.epoch)
        ests.append(per_epoch)
    return epochs, ests


@pytest.mark.parametrize("impl", IMPLS)
def test_fleet_matches_host_monitor_per_epoch(impl):
    """Fused estimates match the float64 oracle within rtol=1e-4 for
    every epoch, with epoch counts identical."""
    cfg = MonitorConfig()
    tc, blocked = _noisy_streams()
    h_epochs, h_ests = _host_epochs(cfg, tc, blocked)
    assert sum(h_epochs) >= 5      # exercise resets

    st, out = run_monitor_fleet(cfg, tc, blocked, chunk_t=256, impl=impl,
                                block_q=8)
    np.testing.assert_array_equal(np.asarray(st.epoch), h_epochs)
    conv = np.asarray(out.converged)
    est = np.asarray(out.estimate)
    for q in range(tc.shape[0]):
        got = est[q][conv[q]]
        np.testing.assert_allclose(got, h_ests[q], rtol=1e-4)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("cfg", [MonitorConfig(),
                                 MonitorConfig(sigma_mode="stderr"),
                                 MonitorConfig.paper_faithful()])
def test_fleet_matches_run_monitor_outputs(impl, cfg):
    """(Q, T) outputs are step-for-step identical to vmap(run_monitor):
    epochs and convergence flags exact, q/q-bar/estimates to 1e-4."""
    tc, blocked = _noisy_streams(Q=4, T=600, seed=3)
    ref = jax.vmap(lambda t, b: run_monitor(cfg, t, b))(
        jnp.asarray(tc, jnp.float32), jnp.asarray(blocked))
    st, out = run_monitor_fleet(cfg, tc, blocked, chunk_t=200, impl=impl,
                                block_q=8)
    np.testing.assert_array_equal(np.asarray(out.epoch),
                                  np.asarray(ref.epoch))
    np.testing.assert_array_equal(np.asarray(out.converged),
                                  np.asarray(ref.converged))
    for name in ("q", "qbar", "estimate"):
        a = np.asarray(getattr(out, name))
        b = np.asarray(getattr(ref, name))
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-3)
    # final carried state agrees with the last outputs
    np.testing.assert_array_equal(np.asarray(st.epoch),
                                  np.asarray(ref.epoch[:, -1]))


@pytest.mark.parametrize("impl", IMPLS)
def test_fleet_blocked_samples_are_discarded(impl):
    cfg = MonitorConfig()
    Q, T = 3, 64
    tc = np.full((Q, T), 100.0)
    blocked = np.zeros((Q, T), bool)
    blocked[1] = True                    # queue 1 fully blocked
    st, out = run_monitor_fleet(cfg, tc, blocked, chunk_t=32, impl=impl,
                                block_q=8)
    assert int(st.s_fill[1]) == 0
    assert int(st.n_blocked[1]) == T
    assert int(st.s_fill[0]) == cfg.window
    assert not bool(np.asarray(out.converged)[1].any())


@pytest.mark.parametrize("impl", IMPLS)
def test_fleet_state_carries_across_dispatches(impl):
    """Chunked dispatches must agree exactly with one big dispatch."""
    cfg = MonitorConfig()
    tc, blocked = _noisy_streams(Q=3, T=512, seed=9)
    st_a, out_a = run_monitor_fleet(cfg, tc, blocked, chunk_t=512,
                                    impl=impl, block_q=8)
    st_b = fleet_monitor_init(cfg, 3)
    outs = []
    for t0 in range(0, 512, 128):
        st_b, o = fleet_monitor_scan(
            cfg, st_b, jnp.asarray(tc[:, t0:t0 + 128], jnp.float32),
            jnp.asarray(blocked[:, t0:t0 + 128]), impl=impl, block_q=8)
        outs.append(o)
    np.testing.assert_array_equal(np.asarray(st_a.epoch),
                                  np.asarray(st_b.epoch))
    ep_b = np.concatenate([np.asarray(o.epoch) for o in outs], axis=1)
    np.testing.assert_array_equal(np.asarray(out_a.epoch), ep_b)
    np.testing.assert_allclose(np.asarray(st_a.mean),
                               np.asarray(st_b.mean), rtol=2e-4, atol=1e-3)


def test_state_mode_matches_full_mode():
    cfg = MonitorConfig()
    tc, blocked = _noisy_streams(Q=4, T=400, seed=5)
    st_full, _ = run_monitor_fleet(cfg, tc, blocked, impl="rounds",
                                   mode="full")
    st_state, out = run_monitor_fleet(cfg, tc, blocked, impl="rounds",
                                      mode="state")
    assert out is None
    for a, b in zip(st_full, st_state):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_fleet_on_simulated_tandem_queues():
    """End-to-end: simulated tandem fleets converge to the configured
    consumer service rates (paper Fig. 13 tolerance)."""
    cfg = MonitorConfig()
    mus = [2.0e5, 1.5e5, 2.5e5]
    results = [simulate_tandem(TandemConfig(mu_a=2 * mu, mu_b=mu,
                                            n_items=120_000, seed=i))
               for i, mu in enumerate(mus)]
    tc, blocked = sample_periods_fleet(results, 1e-3)
    st, _ = run_monitor_fleet(cfg, tc, blocked, impl="rounds",
                              mode="state")
    assert all(int(e) >= 1 for e in np.asarray(st.epoch))
    rates = np.asarray(st.last_qbar) / 1e-3
    np.testing.assert_allclose(rates, mus, rtol=0.2)


def test_fleet_monitor_step_sigma_mode():
    """fleet_monitor_step honors MonitorConfig.sigma_mode."""
    from repro.kernels.monitor.ops import fleet_monitor_step, \
        fleet_step_init
    rng = np.random.default_rng(2)
    Q, W = 6, 32
    win = jnp.asarray(rng.uniform(50, 150, (Q, W)), jnp.float32)

    cfg_w = MonitorConfig()                        # window_std (default)
    st = fleet_step_init(cfg_w, Q)
    sigmas = []
    for _ in range(cfg_w.conv_window + 1):
        q, st, sigma = fleet_monitor_step(win, st, cfg=cfg_w)
        sigmas.append(np.asarray(sigma))
    # not enough q-bar history -> sentinel; full ring -> finite window std
    assert np.all(sigmas[0] > 1e20)
    assert np.all(sigmas[-1] < 1e20)

    cfg_s = MonitorConfig(sigma_mode="stderr")
    st = fleet_step_init(cfg_s, Q)
    q, st, sigma = fleet_monitor_step(win, st, cfg=cfg_s)
    wf = st.welford
    expect = np.sqrt(np.maximum(np.asarray(wf.m2), 0)
                     / np.asarray(wf.count) ** 2)
    np.testing.assert_allclose(np.asarray(sigma), expect, rtol=1e-5,
                               atol=1e-7)


def test_fleet_monitor_service_over_instrumented_queues():
    """streams.FleetMonitorService: one sampling loop, batched estimator."""
    from repro.streams import FleetMonitorService, InstrumentedQueue

    queues = [InstrumentedQueue(capacity=8) for _ in range(3)]
    rates = [120, 240, 360]
    emitted = []
    svc = FleetMonitorService(queues, MonitorConfig(), period_s=1e-3,
                              chunk_t=32, scale_to_period=False,
                              on_converged=lambda qi, r:
                              emitted.append((qi, r)))
    for step in range(150):
        for queue, rate in zip(queues, rates):
            for _ in range(rate):
                queue.push(object())
                queue.pop()
        svc.sample()
    svc.flush()
    assert len(svc) == 3
    eps = svc.epochs()
    assert (eps >= 1).all()
    assert emitted and {qi for qi, _ in emitted} <= {0, 1, 2}
    got = svc.rates_items_per_s() * 1e-3      # items/period
    np.testing.assert_allclose(got, rates, rtol=0.05)


@pytest.mark.parametrize("C,n,smax", [(48, 16, 32), (34, 2, 32),
                                      (288, 32, 256), (64, 32, 32),
                                      (24, 16, 8), (10, 4, 0)])
def test_rounds_row_selects_match_take_along_axis(C, n, smax):
    """The rounds form's gather-free selects return exactly what
    ``take_along_axis`` with clipped indices returns."""
    from repro.kernels.monitor.rounds import row_pick, row_shift
    rng = np.random.default_rng(C * 1000 + n)
    Q = 1000
    x = rng.normal(size=(Q, C)).astype(np.float32)
    x[0, :] = -0.0
    x[1, ::3] = np.inf
    x[2, 1::3] = -np.inf
    s = rng.integers(0, smax + 1, (Q, 1)).astype(np.int32)
    s[:2], s[2:4] = 0, smax
    idx = np.clip(s + np.arange(n)[None, :], 0, C - 1)
    got = np.asarray(jax.jit(row_shift, static_argnums=(2, 3))(
        jnp.asarray(x), jnp.asarray(s), n, smax))
    want = np.take_along_axis(x, idx, axis=1)
    assert got.dtype == want.dtype and got.tobytes() == want.tobytes()

    i = rng.integers(-3, C + 3, (Q, 1)).astype(np.int32)
    i[:4] = [[-1], [C], [0], [C - 1]]
    got = np.asarray(jax.jit(row_pick)(jnp.asarray(x), jnp.asarray(i)))
    want = np.take_along_axis(x, np.clip(i, 0, C - 1), axis=1)
    assert got.dtype == want.dtype and got.tobytes() == want.tobytes()


@pytest.mark.parametrize("mode", ["state", "full"])
def test_rounds_lowers_without_gathers(mode):
    """Per-row gathers run element by element on a TPU; the rounds form
    selects with shifted slices instead, so its HLO holds no gather."""
    from repro.kernels.monitor.rounds import monitor_fleet_rounds
    cfg = MonitorConfig()
    Q, T = 8, 256
    state = fleet_monitor_init(cfg, Q)
    comp = jnp.zeros((Q, T), jnp.float32)
    m = jnp.full((Q,), T, jnp.int32)
    step = jax.jit(lambda st, c, mm: monitor_fleet_rounds(
        cfg, st, c, mm, mode=mode))
    hlo = step.lower(state, comp, m).as_text()
    assert "gather" not in hlo.lower()


def _compact_scatter(tc, blocked):
    """Stream compaction as a per-row scatter: the form ``_compact``
    replaced, kept as its reference."""
    Q, T = tc.shape
    valid = jnp.logical_not(blocked)
    cnt = jnp.cumsum(valid.astype(jnp.int32), axis=1)
    rows = jnp.arange(Q)[:, None]
    dest = jnp.where(valid, cnt - 1, T)                  # T = dump slot
    comp = jnp.zeros((Q, T + 1), tc.dtype).at[rows, dest].set(tc)[:, :T]
    return comp, cnt[:, -1], cnt


def _compact_numpy(tc, blocked):
    """Stable left compaction row by row, zeros after the valid ones."""
    out = np.zeros_like(tc)
    for q in range(tc.shape[0]):
        kept = tc[q][~blocked[q]]
        out[q, :kept.size] = kept
    return out


def _blocked_mask(kind, Q, T, rng):
    col = np.broadcast_to(np.arange(T)[None, :], (Q, T))
    if kind == "none":
        return np.zeros((Q, T), bool)
    if kind == "all":
        return np.ones((Q, T), bool)
    if kind == "alternating":          # odd rows start blocked
        return (col + np.arange(Q)[:, None]) % 2 == 0
    if kind == "first_valid":
        return col != 0
    if kind == "last_valid":
        return col != T - 1
    return rng.random((Q, T)) < float(kind.removeprefix("random"))


@pytest.mark.parametrize("kind", ["none", "all", "alternating",
                                  "random0.1", "random0.5", "random0.9",
                                  "first_valid", "last_valid"])
@pytest.mark.parametrize("T", [1, 7, 32, 200, 256, 257])
def test_compact_matches_scatter_reference(T, kind):
    """The shift-and-select ladder returns the scatter form's bytes,
    signed zeros, infinities and NaN payloads included, and the same
    valid counts."""
    from repro.kernels.monitor.ops import _compact
    Q = 64
    rng = np.random.default_rng(T * 100 + len(kind))
    tc = rng.normal(size=(Q, T)).astype(np.float32)
    tc[:, 0::5] = -0.0
    tc[:, 1::5] = np.inf
    tc[:, 2::5] = -np.inf
    tc[:, 3::7] = np.array([0x7FC12345], np.uint32).view(np.float32)[0]
    tc[::3, 4::9] = np.array([0xFFC00ABC], np.uint32).view(np.float32)[0]
    blocked = _blocked_mask(kind, Q, T, rng)

    comp, m, cnt = jax.jit(_compact)(jnp.asarray(tc), jnp.asarray(blocked))
    r_comp, r_m, r_cnt = jax.jit(_compact_scatter)(jnp.asarray(tc),
                                                   jnp.asarray(blocked))
    got = np.asarray(comp)
    assert got.dtype == np.float32 and got.shape == (Q, T)
    assert got.tobytes() == np.asarray(r_comp).tobytes()
    assert got.tobytes() == _compact_numpy(tc, blocked).tobytes()
    np.testing.assert_array_equal(np.asarray(m), np.asarray(r_m))
    np.testing.assert_array_equal(np.asarray(m), (~blocked).sum(axis=1))
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(r_cnt))


@pytest.mark.parametrize("mode", ["state", "full"])
@pytest.mark.parametrize("chunk_t", [32, 200, 256])
@pytest.mark.parametrize("cfg", [MonitorConfig(),
                                 MonitorConfig(sigma_mode="stderr"),
                                 MonitorConfig.paper_faithful()])
def test_fleet_bit_identical_to_scatter_compaction(cfg, chunk_t, mode,
                                                   monkeypatch):
    """``run_monitor_fleet`` over the ladder compaction ends in the same
    state bytes, and in full mode the same output planes, as over the
    scatter form it replaced."""
    from repro.core import monitor
    from repro.kernels.monitor import ops
    tc, blocked = _noisy_streams(Q=6, T=700, seed=chunk_t, p_block=0.3)
    blocked[4] = True                      # one queue never valid
    blocked[5, :350] = False               # one long valid run

    def run():   # the jitted steps are cached: trace afresh each time
        monitor._fleet_dispatch.cache_clear()
        return run_monitor_fleet(cfg, tc, blocked, chunk_t=chunk_t,
                                 mode=mode, block_q=8)

    try:
        st, out = run()
        monkeypatch.setattr(ops, "_compact", _compact_scatter)
        r_st, r_out = run()
    finally:   # no step traced with the patch may serve another test
        monitor._fleet_dispatch.cache_clear()
    assert float(np.asarray(st.count).sum()) > 0     # q's were folded
    for a, b in zip(st, r_st):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert (out is None) == (mode == "state")
    if out is not None:
        for a, b in zip(out, r_out):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


@pytest.mark.parametrize("mode", ["state", "full"])
def test_compact_lowers_without_scatter_or_sort(mode):
    """A TPU v5e lowers a per-row scatter to a sort of every destination
    key; the fleet step compacts with shifted selects instead, so with a
    blocked plane its HLO holds no scatter and no sort (and, in state
    mode, no gather either)."""
    cfg = MonitorConfig()
    Q, T = 8, 256
    state = fleet_monitor_init(cfg, Q)
    tc = jnp.zeros((Q, T), jnp.float32)
    blocked = jnp.zeros((Q, T), jnp.bool_)
    hlo = fleet_monitor_scan.lower(cfg, state, tc, blocked, impl="rounds",
                                   mode=mode).as_text()
    banned = ["scatter", "sort"] + (["gather"] if mode == "state" else [])
    for op in banned:
        assert not re.search(rf"stablehlo\.{op}\b", hlo), op
