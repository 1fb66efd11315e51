"""Micro-benchmarks for the Pallas kernels (interpret-mode correctness +
jnp-reference timing on CPU; the BlockSpec layout is the TPU contract)."""

from __future__ import annotations

import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.attention.ref import attention_ref
from repro.kernels.monitor.ref import batched_monitor_ref
from repro.kernels.ssd.ref import ssd_chunk_ref
from repro.models.ssm import ssd_chunked

BENCH_MONITOR_JSON = pathlib.Path(__file__).resolve().parent.parent \
    / "BENCH_monitor.json"


def _time(fn, *args, n=5):
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = jax.block_until_ready(fn(*args))
    return out, (time.perf_counter() - t0) / n * 1e6


def monitor_fleet_throughput():
    """Fleet monitor: queues/second the batched window stage sustains."""
    rows = []
    f = jax.jit(lambda w: batched_monitor_ref(w)[0])
    for q in (256, 4096, 65_536):
        win = jax.random.uniform(jax.random.PRNGKey(q), (q, 32)) * 100
        _, us = _time(f, win)
        rows.append(f"kernel_monitor/q={q},{us:.0f},"
                    f"{q / us * 1e6:.2e}_queues_per_s")
    return rows, "fleet monitor scales linearly in queue count"


def monitor_fleet_scan():
    """Fused time-batched Algorithm-1 fleet scan vs the seed per-sample
    paths; writes the perf trajectory to BENCH_monitor.json.

    Throughput metric: samples*queues consumed per second at T=256.
    Baselines (both at Q=4096): (a) the seed per-sample ``lax.scan`` over
    ``monitor_update`` vmapped across the fleet, (b) the seed per-tick
    fleet path (shift window + Pallas Eq. 2+3 window kernel in interpret
    mode + Welford fold, scanned over T).
    """
    from repro.core.monitor import (MonitorConfig, fleet_monitor_init,
                                    run_monitor)
    from repro.core.stats import Welford, welford_update
    from repro.kernels.monitor.kernel import batched_monitor_pallas
    from repro.kernels.monitor.ops import fleet_monitor_scan as scan_op

    cfg = MonitorConfig()
    T = 256
    rng = np.random.default_rng(0)
    rows = []
    report: dict = {"T": T, "config": "MonitorConfig()", "fleet": {},
                    "baselines": {}}

    def bench(fn, *args, n=2):
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(n):
            jax.block_until_ready(fn(*args))
        return (time.perf_counter() - t0) / n

    # --- baselines at Q=4096 -------------------------------------------
    Qb = 4096
    tc_b = jnp.asarray(rng.poisson(200, (Qb, T)), jnp.float32)
    blk_b = jnp.asarray(rng.random((Qb, T)) < 0.05)

    base_scan = jax.jit(jax.vmap(lambda t, b: run_monitor(cfg, t, b).epoch))
    dt = bench(base_scan, tc_b, blk_b)
    report["baselines"]["per_sample_scan_q4096"] = {
        "ms": dt * 1e3, "mqs_per_s": Qb * T / dt / 1e6}
    rows.append(f"monitor_scan/baseline_scan_q={Qb},{dt*1e6:.0f},"
                f"{Qb*T/dt/1e6:.2f}_Mqs_per_s")

    W = cfg.window

    def tick(carry, x):
        win, wf = carry
        win = jnp.concatenate([win[:, 1:], x[:, None]], axis=1)
        q, _, _ = batched_monitor_pallas(win, interpret=True)
        return (win, jax.vmap(welford_update)(wf, q)), ()

    @jax.jit
    def per_tick(tc):
        z = jnp.zeros((Qb,), jnp.float32)
        carry = (jnp.zeros((Qb, W), jnp.float32), Welford(z, z, z))
        (w, wf), _ = jax.lax.scan(tick, carry, tc)
        return wf.mean

    dt = bench(per_tick, tc_b.T, n=1)
    report["baselines"]["per_tick_pallas_interpret_q4096"] = {
        "ms": dt * 1e3, "mqs_per_s": Qb * T / dt / 1e6}
    rows.append(f"monitor_scan/baseline_tick_q={Qb},{dt*1e6:.0f},"
                f"{Qb*T/dt/1e6:.2f}_Mqs_per_s")

    # --- fused fleet scan ----------------------------------------------
    f_clean = jax.jit(lambda s, t: scan_op(
        cfg, s, t, None, impl="rounds", mode="state")[0].epoch)
    f_blk = jax.jit(lambda s, t, b: scan_op(
        cfg, s, t, b, impl="rounds", mode="state")[0].epoch)
    for q in (256, 4096, 65_536):
        tc = jnp.asarray(rng.poisson(200, (q, T)), jnp.float32)
        st0 = fleet_monitor_init(cfg, q)
        cases = [("clean", None)]
        if q <= 4096:   # blocked adds a compaction pass; sample it once
            cases.append(("blocked5pct",
                          jnp.asarray(rng.random((q, T)) < 0.05)))
        for label, b in cases:
            if b is None:
                dt = bench(f_clean, st0, tc)
            else:
                dt = bench(f_blk, st0, tc, b)
            report["fleet"].setdefault(f"rounds_state_{label}", {})[
                str(q)] = {"ms": dt * 1e3, "mqs_per_s": q * T / dt / 1e6}
            rows.append(f"monitor_scan/rounds_{label}_q={q},{dt*1e6:.0f},"
                        f"{q*T/dt/1e6:.2f}_Mqs_per_s")

    # the fused VMEM kernel in interpret mode on every backend, as the
    # row's name says (like the per-tick baseline above), for record
    st0 = fleet_monitor_init(cfg, Qb)
    f = jax.jit(lambda s, t: scan_op(cfg, s, t, None, impl="pallas",
                                     mode="full", interpret=True)[0].epoch)
    dt = bench(f, st0, tc_b, n=1)
    report["fleet"]["pallas_interpret_q4096"] = {
        "ms": dt * 1e3, "mqs_per_s": Qb * T / dt / 1e6}
    rows.append(f"monitor_scan/pallas_interpret_q={Qb},{dt*1e6:.0f},"
                f"{Qb*T/dt/1e6:.2f}_Mqs_per_s")

    fleet = report["fleet"]["rounds_state_clean"]["4096"]["mqs_per_s"]
    s_scan = fleet / report["baselines"][
        "per_sample_scan_q4096"]["mqs_per_s"]
    s_tick = fleet / report["baselines"][
        "per_tick_pallas_interpret_q4096"]["mqs_per_s"]
    report["speedup_vs_per_sample_scan_q4096"] = s_scan
    report["speedup_vs_per_tick_interpret_q4096"] = s_tick
    BENCH_MONITOR_JSON.write_text(json.dumps(report, indent=2))
    return rows, (f"fused fleet scan {s_scan:.1f}x vs per-sample scan, "
                  f"{s_tick:.1f}x vs per-tick interpret fleet path "
                  f"(Q=4096, T=256; see BENCH_monitor.json)")


def ssd_chunk_flops():
    B, S, H, P, N = 2, 2048, 8, 64, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    f = jax.jit(lambda *a: ssd_chunked(*a, chunk=256)[0])
    _, us = _time(f, x, dt, A, Bm, Cm)
    nc = S // 256
    flops = 2 * B * nc * 256 * 256 * (N + H * P) \
        + 4 * B * nc * 256 * H * P * N
    return ([f"kernel_ssd/s={S},{us:.0f},{flops / us / 1e3:.1f}_GFLOPs"],
            "chunked SSD (jnp ref; Pallas kernel is the TPU form)")


def flash_attention_ref_time():
    B, S, H, K, hd = 1, 1024, 8, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, K, hd))
    v = jax.random.normal(ks[2], (B, S, K, hd))
    f = jax.jit(lambda q, k, v: attention_ref(q, k, v))
    _, us = _time(f, q, k, v)
    flops = 4 * B * H * S * S * hd * 0.5
    return ([f"kernel_attn/s={S},{us:.0f},{flops / us / 1e3:.1f}_GFLOPs"],
            "causal attention reference")


ALL = [monitor_fleet_throughput, monitor_fleet_scan, ssd_chunk_flops,
       flash_attention_ref_time]
