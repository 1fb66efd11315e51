"""Offline re-estimation of a recorded counter trace: ``run_monitor_fleet``.

The recorded trace is the mix's cycle of counts for every end, held on
the host as (ends, ``call_periods``) float32 counts and bool blocked
flags, as a user's logged counters would be.  One pass re-estimates it
from a fresh state, handing the estimator one host block per call and
carrying (and donating) the state from call to call; the result of a
pass is due when its last call has finished.  The window runs whole
passes back to back.  The first pass and one drawn from the seed are
kept and compared with the reference.
"""

from __future__ import annotations

import inspect
import math
import time

import numpy as np

from harness import check, traffic
from harness.window import GcMeter, Tracer

__all__ = ["run"]


def run(cell, t0: float) -> dict:
    import jax

    from repro.core import monitor
    defaults = inspect.signature(monitor.run_monitor_fleet).parameters

    c, mix = cell.config, cell.mix
    n = int(c["n_links"])
    s = 2 * n
    mc = c["monitor"]
    cfg = monitor.MonitorConfig(**mc)
    links = traffic.draw_links(mix, n, cell.seed)
    blocks = traffic.draw_counts(mix, links, cell.seed, end_major=True,
                                 block=int(c["call_periods"]))
    rec = traffic.Recorded(blocks, np.arange(s), end_major=True)

    def one_pass(span):
        state = None
        for cnt, blk in blocks:
            with span("bench.call"):
                state, _ = monitor.run_monitor_fleet(
                    cfg, cnt, blk, state=state, mode="state", donate=True)
        with span("bench.wait"):
            jax.block_until_ready(state)
        return state

    tracer = Tracer(cell.trace)
    span = tracer.span
    one_pass(span)                              # compiles every program
    setup_s = time.perf_counter() - t0

    rng = np.random.default_rng(np.random.SeedSequence([cell.seed, 13]))
    gcm = GcMeter(tracer)
    kept, passes = {}, 0
    gcm.start()
    w0 = time.perf_counter()
    tracer.start()      # traced runs: the first whole passes of the window
    while True:
        state = one_pass(span)
        if time.perf_counter() - w0 >= float(c["trace_seconds"]):
            tracer.stop()
        passes += 1
        if passes == 1:
            kept["first"] = state
        elif rng.random() < 1.0 / (passes - 1):   # a uniform later pass
            kept["drawn"] = state
        del state
        if time.perf_counter() - w0 >= cell.seconds:
            break
    w1 = time.perf_counter()
    gcm.stop()
    tracer.stop()

    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    results = {}
    for name, st in kept.items():
        results[name] = {
            "epoch": np.asarray(st.epoch),
            "gated": monitor.fleet_rate_readout(cfg, st),
            "n_total": np.asarray(st.n_total),
            "n_blocked": np.asarray(st.n_blocked),
            "s_fill": np.asarray(st.s_fill), "win": np.asarray(st.win),
            "count": np.asarray(st.count)}
    kept.clear()
    del blocks

    ends = check.covered_ends(np.flatnonzero(links.stepped), s,
                              int(c["ref_ends"]), cell.seed)
    t_pass = rec.period
    ref = check.reference(mc, rec, ends, t_pass)
    numbers = {}
    for prog in results.values():
        got = check.exact_numbers(prog, t_pass, rec, mc["window"])
        got.update(check.alg1_numbers(prog["epoch"][ends],
                                      prog["count"][ends],
                                      prog["gated"][ends], ref))
        for k, v in got.items():       # the worse pass; NaN stays NaN
            numbers[k] = (v if k not in numbers or math.isnan(v)
                          else max(numbers[k], v))
    control = (check.controls(mc, rec, ends, t_pass, ref) if cell.control
               else None)

    return {
        "setup_s": setup_s,
        "window_s": w1 - w0,
        "folded": float(s) * t_pass * passes,
        "attempted": passes,
        "failed": 0,
        "gc_s": gcm.seconds,
        "gc_collections": gcm.collections,
        "tracer": tracer,
        "ends": s,
        "periods_per_dispatch": int(c["call_periods"]),
        "memory_peak_bytes": int(peak or 0),
        "numbers": numbers,
        "control": control,
        "periods": t_pass * passes,
        "settings": {k: p.default for k, p in defaults.items()
                     if k in ("impl", "chunk_t")},
    }
