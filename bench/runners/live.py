"""Live fleet monitoring: ``FleetMonitorService`` driven tick by tick.

The fleet is built as a deployment builds it: ``InstrumentedQueue()``
per monitored link, all in the default arena, and one service over both
ends of every link.  A closed loop then writes one period's counts into
the arena (one vectorised copy of the counts and one of the blocked
flags) and calls ``sample()``, back to back.  The window ends with
``flush()`` at the first chunk boundary after ``--seconds``, so only
harvested work counts and every dispatch has the warmed-up shape; the
loop then runs on for ``drain_periods`` (whole chunks) so that rate
steps near the close get their fresh estimates, and the final state is
read back for the comparison.
"""

from __future__ import annotations

import time

import numpy as np

from harness import check, traffic
from harness.window import GcMeter, Tracer

__all__ = ["run"]


def _lags(times, periods, epochs, ends, k_need, t_switch, t_write):
    """Lag of every switch: the first epoch readout at which the end's
    epoch count reached ``k_need``, and no earlier than the switch."""
    times = np.asarray(times)
    periods = np.asarray(periods)
    ep = np.stack(epochs) if epochs else np.zeros((0, 0), np.int64)
    lag_s, lag_p, failed = [], [], 0
    for col, k, ts, tw in zip(ends, k_need, t_switch, t_write):
        i = int(np.searchsorted(ep[:, col], k)) if ep.size else 0
        i = max(i, int(np.searchsorted(times, tw)))
        if i >= len(times):
            failed += 1
            continue
        lag_s.append(times[i] - tw)
        lag_p.append(periods[i] - ts)
    return np.array(lag_s), np.array(lag_p), failed


def run(cell, t0: float) -> dict:
    import jax

    from repro.core.monitor import MonitorConfig
    from repro.streams.fleet import FleetMonitorService
    from repro.streams.queue import InstrumentedQueue

    c, mix = cell.config, cell.mix
    n = int(c["n_links"])
    s = 2 * n
    mc = c["monitor"]
    cfg = MonitorConfig(**mc)
    links = traffic.draw_links(mix, n, cell.seed)

    queues = [InstrumentedQueue() for _ in range(n)]
    service = FleetMonitorService(queues, cfg, ends=c["ends"],
                                  scale_to_period=c["scale_to_period"])
    arena = queues[0].arena
    slots = np.array([q.head.slot for q in queues]
                     + [q.tail.slot for q in queues])
    order = np.argsort(slots, kind="stable")   # feed column -> public end
    srt = slots[order]
    target = (slice(int(srt[0]), int(srt[0]) + s)
              if np.array_equal(srt, np.arange(srt[0], srt[0] + s)) else srt)
    blocks = traffic.draw_counts(mix, links, cell.seed, columns=order)
    rec = traffic.Recorded(blocks, np.argsort(order), end_major=False)
    bsz, period = rec.block, rec.period

    def feed(t):
        cnt, blk = blocks[(t % period) // bsz]
        r = t % bsz
        arena.tc[target] = cnt[r]
        arena.blocked[target] = blk[r]

    stepped = np.flatnonzero(links.stepped)
    chunk_t, impl = service.chunk_t, service.impl
    t_fed = 0
    service.warmup()
    for _ in range(int(c["warmup_periods"])):
        feed(t_fed)
        service.sample()
        t_fed += 1
    service.flush()
    service.epochs()
    t_start = t_fed
    setup_s = time.perf_counter() - t0

    tracer = Tracer(cell.trace)
    gcm = GcMeter(tracer)
    span = tracer.span
    ticks, dispatching, t_write = [], [], []
    rec_t, rec_p, rec_e = [], [], []
    trace_from = max(cell.seconds - float(c["trace_seconds"]), 0.0)
    gcm.start()
    w0 = time.perf_counter()
    while True:
        tw = time.perf_counter()
        with span("bench.feed"):
            feed(t_fed)
        d0 = service.dispatches
        with span("bench.sample"):
            a = time.perf_counter()
            service.sample()
            b = time.perf_counter()
        t_fed += 1
        t_write.append(tw)
        ticks.append(b - a)
        if service.dispatches != d0:
            dispatching.append(True)
            with span("bench.readout"):
                ep = service.epochs()[stepped]
            rec_t.append(b)
            rec_p.append(t_fed)
            rec_e.append(ep)
        else:
            dispatching.append(False)
        if b - w0 >= trace_from and not tracer.active:
            tracer.start()
        # close on a chunk boundary: the closing flush then only
        # harvests, and no partial chunk compiles a new shape
        if b - w0 >= cell.seconds and (t_fed - t_start) % chunk_t == 0:
            break
    with span("bench.flush"):
        service.flush()
        ep = service.epochs()[stepped]
    w1 = time.perf_counter()
    rec_t.append(w1)
    rec_p.append(t_fed)
    rec_e.append(ep)
    gcm.stop()
    tracer.stop()
    t_end = t_fed

    for _ in range(int(c["drain_periods"])):
        tw = time.perf_counter()
        feed(t_fed)
        d0 = service.dispatches
        service.sample()
        t_fed += 1
        t_write.append(tw)
        if service.dispatches != d0:
            rec_t.append(time.perf_counter())
            rec_p.append(t_fed)
            rec_e.append(service.epochs()[stepped])
    service.flush()
    rec_t.append(time.perf_counter())
    rec_p.append(t_fed)
    rec_e.append(service.epochs()[stepped])

    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    snap = service.state_snapshot()
    prog = {"epoch": service.epochs(),
            "gated": np.concatenate([service.service_rates(),
                                     service.arrival_rates()])
            * service.period_s,
            "n_total": snap.n_total, "n_blocked": snap.n_blocked,
            "s_fill": snap.s_fill, "win": snap.win, "count": snap.count}
    service.stop()
    del service, queues, snap

    # the comparison, after the window and with the program's state freed
    ends = check.covered_ends(np.flatnonzero(links.stepped), s,
                              int(c["ref_ends"]), cell.seed)
    numbers = check.exact_numbers(prog, t_fed, rec, mc["window"])
    ref = check.reference(mc, rec, ends, t_fed)
    numbers.update(check.alg1_numbers(prog["epoch"][ends],
                                      prog["count"][ends],
                                      prog["gated"][ends], ref))
    control = (check.controls(mc, rec, ends, t_fed, ref) if cell.control
               else None)

    # time from a rate step to the first readout from an epoch that
    # began at or after it (epoch boundaries as the reference finds them)
    where = {e: i for i, e in enumerate(ends)}
    col = {e: i for i, e in enumerate(stepped)}
    sw_e, sw_t = traffic.switches(mix, links, t_start, t_end)
    k_need = [int(np.sum(ref.conv_periods[where[e]] < t - 1)) + 2
              for e, t in zip(sw_e, sw_t)]
    lag_s, lag_p, failed = _lags(
        rec_t, rec_p, rec_e, [col[e] for e in sw_e], k_need, sw_t,
        [t_write[t - t_start] for t in sw_t])

    return {
        "setup_s": setup_s,
        "window_s": w1 - w0,
        "folded": float(s) * (t_end - t_start),
        "tick_s": np.array(ticks),
        "dispatching": np.array(dispatching),
        "lag_s": lag_s,
        "lag_periods": lag_p,
        "attempted": int(sw_t.size),
        "failed": failed,
        "gc_s": gcm.seconds,
        "gc_collections": gcm.collections,
        "tracer": tracer,
        "ends": s,
        "periods_per_dispatch": chunk_t,
        "memory_peak_bytes": int(peak or 0),
        "numbers": numbers,
        "control": control,
        "periods": t_end - t_start,
        "settings": {"impl": impl, "chunk_t": chunk_t},
    }
