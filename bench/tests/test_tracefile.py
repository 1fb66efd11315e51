"""The trace reduction, on a trace recorded on a TPU v5e (two 256-period
calls of the estimator at 2e5 ends) and on one recorded here."""

import gzip
import json
from pathlib import Path

import numpy as np

from harness import tracefile
from harness.window import Tracer

DATA = Path(__file__).with_name("data")


def _events():
    with gzip.open(DATA / "trace_v5e_two_calls.json.gz", "rt") as f:
        return [tuple(e) for e in json.load(f)]


def test_reduce_recorded_v5e_trace():
    ev = _events()
    r = tracefile.reduce_events(ev, "jit_step")
    (lo, span), = [(t, d) for k, _, n, t, d in ev if n == "bench.traced"]
    hi = lo + span
    assert r["window_s"] == span * 1e-9
    # busy: the union of op intervals, counted here on a 1 us grid
    grid = np.zeros(int(span / 1e3) + 1, bool)
    n_ops = 0
    for k, line, _, t, d in ev:
        if k.startswith("/device:") and line in tracefile.OPS_LINES:
            a, b = max(t, lo), min(t + d, hi)
            if b > a:
                grid[int((a - lo) / 1e3):int(np.ceil((b - lo) / 1e3))] = True
                n_ops += 1
    assert abs(r["busy_s"] - grid.sum() * 1e-6) < 2e-6 * n_ops
    # one whole execution of the dispatch program lies in the window
    whole = [d for k, line, n, t, d in ev if line == tracefile.MODULES_LINE
             and n.startswith("jit_step") and lo <= t and t + d <= hi]
    assert r["estimator_count"] == len(whole) == 1
    assert r["estimator_s"] == whole[0] * 1e-9
    gaps = sum(s for _, s in r["idle_gaps"])
    assert abs(gaps - (r["window_s"] - r["busy_s"])) < 1e-9
    assert {n for n, _ in r["idle_gaps"]} <= {"bench.chunk", "other"}
    assert len(r["device_ops"]) == 10
    assert all(s1 >= s2 for (_, s1), (_, s2) in zip(r["device_ops"],
                                                    r["device_ops"][1:]))


def test_load_reads_the_harness_spans():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    tr = Tracer(True)
    try:
        tr.start()
        with tr.span("bench.work"):
            f(x).block_until_ready()
        tr.stop()
        ev = tracefile.load(tr.dir)
    finally:
        tr.close()
    names = {n for k, _, n, _, _ in ev if k == "host"}
    assert {"bench.traced", "bench.work"} <= names
    r = tracefile.reduce_events(ev, "jit_")
    assert r["window_s"] > 0
