#!/usr/bin/env python3
"""Bring-up smoke: drive the system's main paths once on one TPU chip.

    python chip_smoke.py

One process, no subprocesses, the normal entry points.  Each phase
prints one JSON line of what it checked:

  a. device     JAX's first device is a TPU (prints JAX version, kind).
  b. fleet      ``run_monitor_fleet`` over 2e5 monitored ends, 8
                dispatches of 256 periods, ``mode="state"``: the Pallas
                kernel compiled (not interpreted) and the XLA ``rounds``
                form both agree with the ``scan`` oracle (every end that
                splits is replayed and its cause shown), and converged
                estimates sit within the paper's Fig. 13 band (+-20%)
                of the configured rates.
  c. pipelines  the paper's two applications (Fig. 16 matmul, Fig. 17
                Rabin-Karp) and a closed-loop ``control=True`` pipeline
                through ``Pipeline`` (one line each): correct outputs,
                monitor dispatches, a converged rate estimate driving a
                jitted control decision, no crash, no degradation
                record.
  d. serving    ``Engine`` with internlm2-1.8b at its published widths
                (seeded bf16 parameters) answers 8 requests, each equal
                to a prefill-then-argmax loop written without the engine.

Compile times are printed as set-up, never as a speed metric.  Any
failed check exits 1 and never prints the final line, which is

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}
"""

from __future__ import annotations

import contextlib
import itertools
import json
import os
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.backend import (enable_compile_cache, on_host,  # noqa: E402
                                resolve_interpret)
from repro.core.monitor import (MonitorConfig, MonitorOutput,  # noqa: E402
                                _fleet_dispatch, fleet_monitor_init,
                                run_monitor_fleet)
from repro.kernels.monitor.ref import fleet_static_params  # noqa: E402

FIG13_BAND = 0.20          # paper Fig. 13: estimates within +-20%
PARITY_RTOL = 1e-4         # tests/test_monitor_fleet.py tolerances
PARITY_ATOL = 1e-3
CONTROL_ERRORS = ("E_JIT_DISPATCH", "E_TICK", "E_MONITOR_DEAD")
CONVERGE_S = 120.0         # the closed loop's longest run to convergence
# share of ends whose final state may split from the scan oracle: 4x
# (pallas, 1.25e-4) and ~3x (rounds, 1.085e-3) the first chip reading;
# every split end must still be explained by its replay
SPLIT_LIMIT = {"pallas": 5e-4, "rounds": 3e-3}
# float64 recomputation vs a form's own Eq. 4 decision: the only rounding
# between them is the 3-tap sum and the tolerance product (~1e-6 of tol)
DECISION_SLACK = 1e-5
# a decision this close to the threshold (relative to tol) may flip
# between two compilers: ~6x the largest margin at a flip on the chip
FLIP_SLACK = 5e-4


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


@contextlib.contextmanager
def thread_errors():
    """Collect exceptions that kill any thread inside the block — a
    daemon thread (monitor, control loop, worker) that dies must fail
    the smoke run, not vanish behind the fault-tolerance paths."""
    errors: list[str] = []
    prev = threading.excepthook

    def hook(args):
        errors.append(f"{args.thread.name if args.thread else '?'}: "
                      f"{args.exc_type.__name__}: {args.exc_value}")
        prev(args)

    threading.excepthook = hook
    try:
        yield errors
    finally:
        threading.excepthook = prev


# ---------------------------------------------------------------------------
# a. device
# ---------------------------------------------------------------------------

def phase_device() -> dict:
    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu",
          f"no TPU: JAX's first device is {d.platform!r} ({d.device_kind})")
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    emit("device", jax=jax.__version__, **info)
    return info


# ---------------------------------------------------------------------------
# b. fleet estimator
# ---------------------------------------------------------------------------

def fleet_streams(n_ends: int, chunk_t: int, n_chunks: int, seed: int,
                  p_block: float = 0.1):
    """Seeded per-end Poisson counts at known rates (items per period),
    with ``p_block`` of the periods blocked.  Generated on the device one
    (n_ends, chunk_t) tile at a time; returns (rates, tc, blocked)."""
    key_rate, key_data = jax.random.split(jax.random.PRNGKey(seed))
    rates = jax.random.uniform(key_rate, (n_ends,), jnp.float32,
                               minval=100.0, maxval=400.0)

    @jax.jit
    def tile(key):
        k_cnt, k_blk = jax.random.split(key)
        tc = jax.random.poisson(k_cnt, rates[:, None], (n_ends, chunk_t))
        return (tc.astype(jnp.float32),
                jax.random.bernoulli(k_blk, p_block, (n_ends, chunk_t)))

    tiles = [tile(k) for k in jax.random.split(key_data, n_chunks)]
    tc = jnp.concatenate([t[0] for t in tiles], axis=1)
    blocked = jnp.concatenate([t[1] for t in tiles], axis=1)
    return rates, tc, blocked


def _kernel_is_compiled(cfg: MonitorConfig, n_ends: int, chunk_t: int,
                        block_q: int) -> bool:
    """Whether the dispatch ``run_monitor_fleet(impl="pallas")`` runs
    holds a Mosaic custom call (compiled), not the interpreter's loop."""
    qp = -(-n_ends // block_q) * block_q
    state = jax.eval_shape(lambda: fleet_monitor_init(cfg, qp))
    step = _fleet_dispatch(cfg, "pallas", "state", resolve_interpret(None),
                           block_q, False)
    tile = [jax.ShapeDtypeStruct((qp, chunk_t), dt)
            for dt in (jnp.float32, jnp.bool_)]
    return "tpu_custom_call" in step.lower(state, *tile).as_text()


def _max_rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-12),
                        initial=0.0))


def run_by_dispatch(cfg: MonitorConfig, tc, blocked, impl: str,
                    chunk_t: int, block_q: int) -> list:
    """``run_monitor_fleet`` in ``mode="state"`` one ``chunk_t`` dispatch
    at a time, as the monitoring service drives it: the fleet state
    (device arrays) before the first dispatch and after each one."""
    states = [fleet_monitor_init(cfg, tc.shape[0])]
    for t0 in range(0, tc.shape[1], chunk_t):
        st, out = run_monitor_fleet(
            cfg, tc[:, t0:t0 + chunk_t], blocked[:, t0:t0 + chunk_t],
            state=states[-1], chunk_t=chunk_t, impl=impl, mode="state",
            block_q=block_q)
        check(out is None, f"{impl}: mode='state' returned outputs")
        states.append(st)
    return states


def phase_fleet(n_ends: int = 200_000, chunk_t: int = 256,
                n_chunks: int = 8, block_q: int = 256,
                seed: int = 0) -> dict:
    cfg = MonitorConfig()
    interpret = resolve_interpret(None)
    compiled = _kernel_is_compiled(cfg, n_ends, chunk_t, block_q)
    check(interpret == on_host() and compiled != interpret,
          f"pallas interpret={interpret} compiled={compiled} on "
          f"{jax.default_backend()}")
    rates, tc, blocked = fleet_streams(n_ends, chunk_t, n_chunks, seed)
    jax.block_until_ready((tc, blocked))

    bounds, setup_s = {}, {}
    for impl in ("pallas", "rounds", "scan"):
        # one throwaway dispatch compiles the step (set-up, not speed)
        t0 = time.perf_counter()
        warm, _ = run_monitor_fleet(
            cfg, jnp.zeros((n_ends, chunk_t)),
            jnp.ones((n_ends, chunk_t), bool), chunk_t=chunk_t, impl=impl,
            mode="state", block_q=block_q)
        jax.block_until_ready(warm)
        setup_s[impl] = round(time.perf_counter() - t0, 2)
        bounds[impl] = run_by_dispatch(cfg, tc, blocked, impl, chunk_t,
                                       block_q)
    states = {impl: jax.tree_util.tree_map(np.asarray, b[-1])
              for impl, b in bounds.items()}

    ref = states["scan"]
    parity = {impl: _parity(cfg, tc, blocked, bounds[impl], bounds["scan"],
                            impl, chunk_t, block_q)
              for impl in ("pallas", "rounds")}
    rates = np.asarray(rates, np.float64)
    n_periods = chunk_t * n_chunks
    band = {}
    for impl, st in states.items():
        conv = st.epoch >= 1
        rel = np.abs(st.last_qbar[conv] - rates[conv]) / rates[conv]
        band[impl] = {"converged_frac": float(conv.mean()),
                      "max_rel_err_vs_rate": float(rel.max(initial=0.0)),
                      "in_band_frac": float(np.mean(rel <= FIG13_BAND))
                      if conv.any() else 0.0}
    result = {
        "ends": n_ends, "chunk_t": chunk_t, "dispatches": n_chunks,
        "periods_per_end": n_periods, "interpret": interpret,
        "mosaic_kernel": compiled,
        "blocked_frac": float(ref.n_blocked.sum() / ref.n_total.sum()),
        "mean_epochs": float(ref.epoch.mean()), "fig13_band": band,
        "parity_vs_scan": parity,
        "setup_s": {"compile_plus_one_dispatch": setup_s}}
    emit("fleet", **result)

    check(int(ref.n_total[0]) == n_periods,
          f"n_total {int(ref.n_total[0])} != {n_periods}")
    for impl, b in band.items():
        check(b["converged_frac"] >= 0.99,
              f"{impl}: only {b['converged_frac']:.4f} of ends converged")
        check(b["in_band_frac"] == 1.0,
              f"{impl}: converged ends outside the +-{FIG13_BAND:.0%} band")
    for impl, p in parity.items():
        check_parity(impl, p)
    return result


def check_parity(impl: str, p: dict) -> None:
    check(p["unexplained"] == 0,
          f"{impl}: {p['unexplained']} of {p['split_ends']} ends split from "
          f"the scan oracle without a near-threshold cause: {p['causes']}")
    check(p["split_frac"] <= SPLIT_LIMIT[impl],
          f"{impl} splits from the scan oracle at {p['split_ends']} ends "
          f"({p['split_frac']:.3g}, limit {SPLIT_LIMIT[impl]:.0e})")


def _close(a, b) -> np.ndarray:
    return np.isclose(a, b, rtol=PARITY_RTOL, atol=PARITY_ATOL)


def _parts(a, b) -> np.ndarray:
    """Ends whose fleet states ``a`` and ``b`` part: epochs or fold
    counts differ, or the estimate or running q-bar falls outside the
    tests' tolerance."""
    return ((a.epoch != b.epoch) | (a.count != b.count)
            | ~_close(a.last_qbar, b.last_qbar) | ~_close(a.mean, b.mean))


def eq4_margins(P, st, out: MonitorOutput, ready) -> np.ndarray:
    """Eq. 4 along one end's replayed dispatch, recomputed in float64:
    the LoG responses from the sigma the replay emitted (and the response
    history and sigma pair of ``st``, the end's state when the dispatch
    began), the tolerance from its q-bar.  Per step, ``(tol - response)
    / tol`` where the end was ready to test, ``-inf`` elsewhere; the
    replay's own convergences reset the fold count, as Algorithm 1
    does."""
    l0, l1, l2 = P.log_taps
    count = float(st.count)
    s2 = [float(s) for s in st.shist]
    resp = [float(r) for r in st.rhist]
    margin = np.full(ready.shape, -np.inf)
    for t in np.nonzero(ready)[0]:
        count += 1
        sig = float(out.sigma_qbar[t])
        if count >= 3:
            resp = resp[1:] + [l0 * s2[0] + l1 * s2[1] + l2 * sig]
        s2 = [s2[1], sig]
        r = max(abs(x) for x in resp)
        if count >= max(P.conv_window + 2, P.min_q) and np.isfinite(r):
            tol = P.conv_tol * (max(abs(float(out.qbar[t])), 1e-12)
                                if P.rel_tol else 1.0)
            margin[t] = (tol - r) / tol
        if out.converged[t]:
            count = 0.0
    return margin


def _rows(tree, rows):
    return jax.tree_util.tree_map(lambda a: np.asarray(a[rows]), tree)


def _parity(cfg, tc, blocked, bounds, bounds_ref, impl: str,
            chunk_t: int, block_q: int) -> dict:
    """How far ``impl``'s final fleet state agrees with the scan
    oracle's, and why the ends that split do.  ``bounds`` and
    ``bounds_ref`` are the two forms' states at every dispatch boundary
    (``run_by_dispatch``).

    An end *splits* when its epoch count differs or its estimate falls
    outside the tests' tolerance.  Each Eq. 4 decision thresholds a
    second difference of float32 sigmas, so across 2e5 ends two
    compilers' roundings flip a few decisions that sit at the threshold;
    after a flip the two trajectories part for good.  A split end is
    *explained* only if, in the dispatch where the two forms' states
    first part (they agree at its start), full-mode replays of that
    dispatch from each form's own starting state show

    (a) every decision each replay took is the one a float64
        recomputation from its own sigma and q-bar gives, or that
        recomputation sits within ``DECISION_SLACK`` of the threshold;
    (b) a decision within ``FLIP_SLACK`` of the threshold, at or before
        the first step where the replays' q, q-bar or sigma part.

    The replays are programs other than the compared ``mode="state"``
    ones, so at such a decision they may go either way; they are
    evidence that the threshold was within rounding, not a rerun."""
    P = fleet_static_params(cfg)
    fin, fin_ref = (jax.tree_util.tree_map(np.asarray, b[-1])
                    for b in (bounds, bounds_ref))
    split = np.nonzero((fin.epoch != fin_ref.epoch)
                       | ~_close(fin.last_qbar, fin_ref.last_qbar))[0]
    out = {"split_ends": int(split.size),
           "split_frac": float(split.size / fin.epoch.size),
           "max_epoch_diff": int(np.max(np.abs(fin.epoch - fin_ref.epoch),
                                        initial=0)),
           "max_rel_diff_agreeing": _max_rel(
               np.delete(fin.last_qbar, split),
               np.delete(fin_ref.last_qbar, split))}
    causes = dict.fromkeys(("decision_off", "no_near_decision"), 0)
    nearest, replays_flip = [], 0
    if split.size:
        rows = jnp.asarray(split)
        by = [(_rows(a, rows), _rows(b, rows))
              for a, b in zip(bounds, bounds_ref)]
        # the first boundary at which each split end's states part
        first = np.argmax(np.stack([_parts(a, b) for a, b in by[1:]]),
                          axis=0) + 1
        for k in np.unique(first):
            t0 = (k - 1) * chunk_t
            tc_k, blk_k = (x[:, t0:t0 + chunk_t] for x in (tc, blocked))
            replays = [_rows(run_monitor_fleet(
                cfg, tc_k, blk_k, state=bds[k - 1], chunk_t=chunk_t,
                impl=form, mode="full", block_q=block_q)[1], rows)
                for form, bds in ((impl, bounds), ("scan", bounds_ref))]
            valid = ~np.asarray(blk_k[rows])
            ready = valid & (by[k - 1][0].s_fill[:, None]
                             + np.cumsum(valid, axis=1) >= cfg.window)
            for i in np.nonzero(first == k)[0]:
                pick = lambda tree: type(tree)(*(x[i] for x in tree))  # noqa
                (ma, a), (mb, b) = (
                    (eq4_margins(P, pick(s), pick(o), ready[i]), pick(o))
                    for s, o in zip(by[k - 1], replays))
                if any(np.any((o.converged != (m > 0))
                              & (np.abs(m) > DECISION_SLACK))
                       for o, m in ((a, ma), (b, mb))):
                    causes["decision_off"] += 1
                    continue
                apart = np.nonzero(~(_close(a.q, b.q) & _close(a.qbar, b.qbar)
                                     & _close(a.sigma_qbar, b.sigma_qbar)))[0]
                t_part = apart[0] if apart.size else chunk_t
                m = np.minimum(np.abs(ma), np.abs(mb))[:t_part + 1]
                if not m.min() <= FLIP_SLACK:
                    causes["no_near_decision"] += 1
                    continue
                nearest.append((float(m.min()), int(t0 + np.argmin(m))))
                replays_flip += bool(np.any(a.converged != b.converged))
    nearest.sort()
    out.update(replayed=int(split.size),
               unexplained=int(sum(causes.values())), causes=causes,
               replays_flip=replays_flip,
               max_nearest_margin=nearest[-1][0] if nearest else 0.0,
               nearest_margins_steps=nearest[-8:])
    return out


# ---------------------------------------------------------------------------
# c. streaming pipelines
# ---------------------------------------------------------------------------

def _check_pipeline(name: str, pipe, queue: str, run_s: float,
                    errors: list) -> dict:
    """The monitor and control checks shared by every pipeline; prints
    the pipeline's line before checking.  Under a control loop the
    instrumented ``queue`` must have converged (epochs >= 1, a non-zero
    rate) on at least one of its ends: the consumer end of a link whose
    consumer is starved, or the producer end of one whose queue stays
    full, blocks nearly every period (the paper's point), so which end
    is observable depends on the host."""
    stats = pipe.stats()
    link = pipe.rates()[queue]
    head_ep, tail_ep = link_epochs(pipe, queue)
    loop = pipe.control
    info = {"app": name, "dispatches": pipe.fleet.dispatches,
            "periods_sampled": int(np.max(pipe.fleet.blocked_counts()[1],
                                          initial=0)),
            "host_wall_s": round(run_s, 2), "queue": queue,
            "service_rate": link["service_rate"],
            "arrival_rate": link["arrival_rate"],
            "head_epochs": head_ep, "tail_epochs": tail_ep,
            "head_blocking_frac": link["blocking_frac"],
            "crashes": stats["crash_count"], "thread_errors": errors}
    if loop is not None:
        health = loop.health()
        codes = sorted({r.error for r in loop.log.records() if r.error})
        info.update(impl=loop.impl, ticks=health["ticks"],
                    impl_degraded=health["impl_degraded"],
                    error_codes=codes,
                    live_replicas=stats["live_replicas"])
    emit("pipelines", **info)

    check(pipe.fleet.dispatches > 0, f"{name}: no monitor dispatch")
    check(pipe.monitor.ident is not None, f"{name}: monitor never ran")
    check(stats["crash_count"] == 0, f"{name}: crashes {stats['crashes']}")
    if loop is not None:
        check(has_converged_rate(pipe, queue),
              f"{name}: no converged estimate on either end of {queue}: "
              f"epochs {head_ep}/{tail_ep}, {link}")
        check(loop.impl == ("numpy" if on_host() else "jit"),
              f"{name}: control impl {loop.impl!r}")
        check(health["ticks"] > 0, f"{name}: control loop never ticked")
        check(not health["impl_degraded"] and health["jit_failures"] == 0
              and health["tick_errors"] == 0
              and health["monitor_restarts"] == 0,
              f"{name}: loop health {health}")
        check(not set(codes) & set(CONTROL_ERRORS),
              f"{name}: control errors {codes}")
    check(not errors, f"{name}: threads died: {errors}")
    return info


def link_epochs(pipe, queue: str) -> tuple[int, int]:
    """Convergence epochs of ``queue``'s consumer and producer ends."""
    i = [q.name for q in pipe.queues].index(queue)
    eps = pipe.fleet.epochs()
    n = len(pipe.queues)
    return int(eps[i]), int(eps[i + n]) if eps.size > n else 0


def has_converged_rate(pipe, queue: str) -> bool:
    """Whether an end of ``queue`` has converged on a non-zero rate: an
    end that sees no items while unblocked (a producer before its
    stream starts) converges on zero, which is no estimate."""
    link = pipe.rates()[queue]
    head_ep, tail_ep = link_epochs(pipe, queue)
    return ((head_ep >= 1 and link["service_rate"] > 0)
            or (tail_ep >= 1 and link["arrival_rate"] > 0))


def _run(pipe) -> tuple[list, float]:
    pipe.fleet.warmup()            # compile before items flow
    if pipe.control is not None:
        pipe.control.warmup()
    t0 = time.perf_counter()
    out = pipe.run_collect(timeout_s=300)
    return out, time.perf_counter() - t0


def phase_pipelines(matmul_n: int = 4096, rk_reps: int = 2_000_000,
                    loop_items: int = 12_000) -> dict:
    """The paper's applications, sized (``matmul_n`` rows, ``rk_reps``
    copies of the pattern) to run for a few seconds, and a closed-loop
    pipeline shaped like ``examples/streaming_apps.py:closed_loop_demo``
    whose source runs ``loop_items`` items and then on until its link
    has converged, for at most ``CONVERGE_S`` seconds.  Prints one line
    per pipeline."""
    from benchmarks.apps import matmul_pipeline, rabin_karp_pipeline
    from repro.streams import Pipeline, Stage

    result = {}
    with thread_errors() as errors:
        # The paper instruments the reduce kernel's in-queue (Fig. 16)
        # and the hash kernels' out-queue (Fig. 17).  Both applications'
        # stages are Python that holds the interpreter lock, and the
        # sampler thread then folds far fewer periods than it asks for
        # (PERF.md): whether these links reach an estimate within the
        # run is up to the host, so only the dispatch is required.
        pipe, correct = matmul_pipeline(matmul_n)
        out, run_s = _run(pipe)
        check(len(out) == matmul_n and correct(),
              f"matmul: {len(out)} rows, correct={correct()}")
        result["fig16_matmul"] = _check_pipeline(
            "fig16_matmul", pipe, "dot->reduce", run_s, errors)

        pipe, expect = rabin_karp_pipeline(rk_reps)
        out, run_s = _run(pipe)
        found = sum(len(x) for x in out)
        check(found == expect, f"rabin-karp: {found} matches != {expect}")
        result["fig17_rabin_karp"] = {"matches": found, **_check_pipeline(
            "fig17_rabin_karp", pipe, "hash->verify", run_s, errors)}

        # the heavy stage sleeps (releases the lock): the sampler keeps
        # its period, and the loop must sense an estimate and act on it
        def heavy(x):
            time.sleep(4e-4)       # I/O-shaped stage: wants replicas
            return x + 1

        def until_converged():
            deadline = time.monotonic() + CONVERGE_S
            for n in itertools.count():
                if n >= loop_items and n % 256 == 0 and (
                        has_converged_rate(pipe, "src->heavy")
                        or time.monotonic() > deadline):
                    return
                yield n

        pipe = Pipeline([Stage("src", source=until_converged()),
                         Stage("heavy", fn=heavy)],
                        capacity=64, base_period_s=1e-3, control=True,
                        monitor_cfg=MonitorConfig(window=16,
                                                  min_q_samples=16))
        out, run_s = _run(pipe)
        check(len(out) >= loop_items
              and sorted(out) == list(range(1, len(out) + 1)),
              f"closed loop: {len(out)} items, not 1..n for n >= "
              f"{loop_items}")
        result["closed_loop"] = _check_pipeline(
            "closed_loop", pipe, "src->heavy", run_s, errors)
    return result


# ---------------------------------------------------------------------------
# d. serving
# ---------------------------------------------------------------------------

def greedy_reference(model, params, prompts: np.ndarray, max_new: int,
                     max_seq: int) -> np.ndarray:
    """Greedy tokens for each prompt row without the engine: one
    prefill, then ``max_new`` argmax steps through the decode path."""
    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step)
    L = prompts.shape[1]
    logits, cache = prefill(params, {"tokens": jnp.asarray(prompts)})

    def to_max_seq(v):          # (layers, batch, seq, ...) caches
        if v.ndim >= 3 and v.shape[2] == L:
            pad = [(0, 0)] * v.ndim
            pad[2] = (0, max_seq - L)
            return jnp.pad(v, pad)
        return v

    cache = jax.tree_util.tree_map(to_max_seq, cache)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    pos = jnp.full((prompts.shape[0],), L, jnp.int32)
    out = []
    for _ in range(max_new):
        out.append(np.asarray(tok))
        tok, cache = decode(params, cache, tok, pos)
        pos = pos + 1
    return np.stack(out, axis=1)


def phase_serve(arch_cfg=None, n_requests: int = 8, prompt_len: int = 32,
                max_new: int = 16, seed: int = 0) -> dict:
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serve import Engine, Request, ServeConfig

    cfg = arch_cfg or get_config("internlm2-1.8b")
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = model.init_params(jax.random.PRNGKey(seed), jnp.bfloat16)
    jax.block_until_ready(params)
    init_s = round(time.perf_counter() - t0, 2)
    leaves = jax.tree_util.tree_leaves(params)
    scfg = ServeConfig(batch_size=n_requests,
                       max_seq=2 * (prompt_len + max_new))
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab_size, (n_requests, prompt_len),
                           dtype=np.int32)
    reqs = [Request(rid=i, tokens=prompts[i], max_new=max_new)
            for i in range(n_requests)]

    with thread_errors() as errors:
        eng = Engine(model, params, scfg).start()
        try:
            t0 = time.perf_counter()
            for r in reqs:
                check(eng.submit(r, timeout=60), f"request {r.rid} refused")
            for r in reqs:
                r.done.wait(timeout=900)
            first_round_s = round(time.perf_counter() - t0, 2)
        finally:
            eng.stop()
        stats = eng.stats()
    answered = [r for r in reqs
                if r.out is not None and len(r.out) == max_new]
    check(len(answered) == n_requests,
          f"{len(answered)}/{n_requests} requests answered in full")
    check(stats["crash_count"] == 0, f"engine crashes {stats['crashes']}")
    check(not errors, f"threads died: {errors}")

    ref = greedy_reference(model, params, prompts, max_new, scfg.max_seq)
    match = [bool(np.array_equal(r.out, ref[r.rid])) for r in reqs]
    check(all(match), f"engine tokens differ from the plain reference "
          f"for requests {[r.rid for r, m in zip(reqs, match) if not m]}")
    result = {
        "arch": cfg.name, "n_layers": cfg.n_layers, "d_model": cfg.d_model,
        "params": int(sum(x.size for x in leaves)),
        "param_bytes": int(sum(x.size * x.dtype.itemsize for x in leaves)),
        "requests": n_requests, "answered": len(answered),
        "prompt_len": prompt_len, "max_new": max_new,
        "served": stats["served"], "crashes": stats["crash_count"],
        "match_reference": sum(match),
        "setup_s": {"param_init": init_s,
                    "first_round_incl_compile": first_round_s}}
    emit("serve", **result)
    return result


def main() -> int:
    # libtpu logs to /tmp by default; keep the run's writes in the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        info = phase_device()
        enable_compile_cache()
        phase_fleet()
        phase_pipelines()
        phase_serve()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
