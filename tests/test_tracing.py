"""Names the fleet estimator puts on its work for a profiler.

The jitted fleet step names Algorithm 1's phases with
``jax.named_scope`` (``monitor.*``, carried as HLO ``op_name``
metadata); ``run_monitor_fleet`` and ``FleetMonitorService`` name their
host stages with profiler spans (``repro.monitor.*``,
``repro.fleet.*``).  Neither changes what the estimator computes.
"""

import glob
import os
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import monitor
from repro.core.backend import resolve_interpret
from repro.core.monitor import MonitorConfig, run_monitor_fleet
from repro.streams import FleetMonitorService, InstrumentedQueue

CFG = MonitorConfig()
Q, T = 40, 64


def _streams(seed=0):
    rng = np.random.default_rng(seed)
    tc = rng.poisson(100.0, (Q, T)).astype(np.float32)
    return tc, rng.random((Q, T)) < 0.1


def _phases(impl):
    """The ``monitor.*`` scopes in the op_name metadata of the lowered
    fleet step."""
    step = monitor._fleet_dispatch(CFG, impl, "state",
                                   resolve_interpret(None), 64, False)
    st = monitor.fleet_monitor_init(CFG, 64)
    tc = jax.ShapeDtypeStruct((64, 32), np.float32)
    blk = jax.ShapeDtypeStruct((64, 32), np.bool_)
    text = step.lower(st, tc, blk).as_text(dialect="hlo", debug_info=True)
    return {part for op in re.findall(r'op_name="([^"]*)"', text)
            for part in op.split("/") if part.startswith("monitor.")}


@pytest.mark.parametrize("impl,want", [
    ("rounds", {"monitor.compact", "monitor.window", "monitor.detect",
                "monitor.carry"}),
    ("pallas", {"monitor.compact", "monitor.layout", "monitor.pallas",
                "monitor.carry"}),
])
def test_fleet_step_names_its_phases(impl, want):
    assert _phases(impl) == want


def _trace(fn, tmp_path):
    """Run ``fn`` under the profiler; its host spans as (name, stats)."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    spans = [(ev.name, dict(ev.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("repro.")]
    return out, spans


def test_traced_run_is_bit_identical(tmp_path):
    tc, blk = _streams()

    def run():
        st, _ = run_monitor_fleet(CFG, tc, blk, chunk_t=32, mode="state",
                                  block_q=64)
        return jax.tree.map(np.asarray, st)

    plain = run()
    traced, spans = _trace(run, tmp_path)
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(traced)):
        np.testing.assert_array_equal(a, b)
    names = [n for n, _ in spans]
    assert names == ["repro.monitor.stage", "repro.monitor.pad",
                     "repro.monitor.dispatch", "repro.monitor.unpad"]


def test_dispatching_tick_spans(tmp_path):
    queues = [InstrumentedQueue(8) for _ in range(Q // 2)]
    svc = FleetMonitorService(queues, CFG, period_s=1e-3, chunk_t=8,
                              scale_to_period=False)
    svc.warmup()
    tc, blk = _streams(1)

    def ticks(t0, n):
        for t in range(t0, t0 + n):
            for qi, q in enumerate(queues):
                q.head.tc = float(tc[qi, t])
                q.head.blocked = bool(blk[qi, t])
            svc.sample()

    ticks(0, 8)                     # dispatch 1
    _, spans = _trace(lambda: ticks(8, 8), tmp_path)   # quiet + dispatch 2
    svc.stop()
    fleet = [(n, s) for n, s in spans if n.startswith("repro.fleet.")]
    assert [n for n, _ in fleet] == ["repro.fleet.collect"] * 8 + [
        f"repro.fleet.{s}" for s in ("harvest", "slo", "transpose",
                                     "classify", "estimate")]
    assert all(s == {"dispatch": 2} for _, s in fleet)
    # the estimator's own spans run inside the tick's
    assert [n for n, _ in spans if n.startswith("repro.monitor.")] == [
        "repro.monitor.stage", "repro.monitor.pad",
        "repro.monitor.dispatch", "repro.monitor.unpad"]
