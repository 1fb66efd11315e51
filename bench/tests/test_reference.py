"""The float64 reference agrees with the program's own float64
host-side monitor, end by end and period by period."""

import dataclasses

import numpy as np

from ref.algorithm1 import counting_state, run_reference
from repro.core.monitor import HostMonitor, MonitorConfig


def test_reference_matches_host_monitor():
    cfg = MonitorConfig()
    rng = np.random.default_rng(20261017)
    t, e = 400, 6
    lam = rng.uniform(100, 400, e)
    lam[0] = 5.0                                   # a slow end too
    cnt = rng.poisson(lam, (t, e)).astype(np.float64)
    cnt[200:, 1] *= 2                              # a rate step
    blk = rng.random((t, e)) < 0.1
    ref = run_reference(cnt, blk, dataclasses.asdict(cfg))
    for i in range(e):
        host = HostMonitor(cfg)
        conv = [p for p in range(t) if host.update(cnt[p, i], bool(blk[p, i]))]
        assert host.epoch == ref.epoch[i] > 0
        assert conv == list(ref.conv_periods[i])
        np.testing.assert_allclose(ref.last_qbar[i], host.last_qbar,
                                   rtol=1e-12)
        assert host._n == ref.count[i]
        np.testing.assert_allclose(ref.mean[i], host._mean, rtol=1e-12)


def test_counting_state_takes_the_last_valid_counts():
    cnt = np.arange(12, dtype=float).reshape(6, 2)   # (periods, ends)
    blk = np.zeros((6, 2), bool)
    blk[5, 0] = blk[2, 1] = True
    nb, fill, win = counting_state(np.array([1, 1]), cnt, blk, 3)
    assert list(nb) == [1, 1] and list(fill) == [3, 3]
    assert win[0].tolist() == [4.0, 6.0, 8.0]
    assert win[1].tolist() == [7.0, 9.0, 11.0]
