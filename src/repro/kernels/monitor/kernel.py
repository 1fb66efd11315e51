"""Pallas TPU kernels: fused fleet-scale service-rate monitor.

Two entry points:

* ``batched_monitor_pallas`` — the original per-tick window stage
  (Eq. 2+3) for (Q, w) windows.  Block shape is *static* (``block_q``),
  the queue axis is padded up to a block multiple and the tail masked off
  by slicing, so varying fleet sizes share one compiled executable
  instead of recompiling per (Q-derived) block shape.

* ``monitor_fleet_pallas`` — the time-batched full Algorithm-1 scan.
  One launch consumes a lane-major (T, Q) tile of compacted samples:
  time on sublanes, queues on lanes, grid over queue blocks.  Per
  program the (w, BQ) window carry, the (conv_w, BQ) q-bar and
  LoG-response histories, and all per-queue scalar state as (1, BQ)
  rows live in VMEM for the whole time loop.  Stage A (Gaussian
  stencil + sliding mean/std via centered shifted-slice ladders) is
  vectorized over the whole tile; the sequential Stage B folds one
  sample per ``fori_loop`` step with O(1) masked-vector work per queue,
  reading and writing one (1, BQ) row per step.  Fleet state never
  round-trips HBM per sample — it is read once per tile and written
  once per tile.

The math lives in ``ref.py`` (``fleet_window_stage`` / ``fleet_step``,
run here with ``axis=0``); this module only adds the memory
choreography, so kernel and oracle cannot drift.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.backend import resolve_interpret
from repro.core.filters import gaussian_kernel
from repro.core.monitor import MonitorConfig, Z_95
from repro.kernels.monitor.ref import (fleet_static_params, fleet_step,
                                       fleet_window_stage)

__all__ = ["monitor_kernel", "batched_monitor_pallas",
           "monitor_fleet_kernel", "monitor_fleet_pallas",
           "N_FSTATE", "N_ISTATE"]

# packed per-queue scalar state rows (see _pack_state in ops.py):
# fstate: [count, mean, m2, last_qbar, pad x4]
# istate: [s_fill, epoch, pad x6]
N_FSTATE = 8
N_ISTATE = 8


# ---------------------------------------------------------------------------
# Per-tick window stage (kept for the per-sample path and its tests).
# ---------------------------------------------------------------------------

def monitor_kernel(win_ref, q_ref, mu_ref, sd_ref, *, taps, n_out, z):
    w = win_ref[...].astype(jnp.float32)            # (BQ, W)
    acc = w[:, 0:n_out] * taps[0]
    for i in range(1, len(taps)):
        acc = acc + w[:, i:i + n_out] * taps[i]     # 5-tap stencil
    mu = jnp.mean(acc, axis=1)
    var = jnp.mean(acc * acc, axis=1) - mu * mu
    sd = jnp.sqrt(jnp.maximum(var, 0.0))
    q_ref[...] = mu + z * sd
    mu_ref[...] = mu
    sd_ref[...] = sd


@functools.partial(jax.jit, static_argnames=("radius", "sigma", "z",
                                             "block_q", "interpret"))
def batched_monitor_pallas(windows, *, radius: int = 2, sigma: float = 1.0,
                           z: float = Z_95, block_q: int = 256,
                           interpret: bool | None = None):
    """windows: (Q, w) -> (q, mu, sd).

    ``block_q`` is the static block shape; Q is padded up to a block
    multiple and the tail rows are masked off by the final slice, so the
    compiled kernel is reused across fleet sizes within the same padded
    bucket (no data-dependent block arithmetic).
    """
    Q, W = windows.shape
    taps = tuple(float(t) for t in
                 gaussian_kernel(radius, sigma, normalize=True))
    n_out = W - 2 * radius
    BQ = block_q
    Qp = -(-Q // BQ) * BQ
    if Qp != Q:
        windows = jnp.pad(windows, ((0, Qp - Q), (0, 0)))

    kernel = functools.partial(monitor_kernel, taps=taps, n_out=n_out,
                               z=float(z))
    out_shape = [jax.ShapeDtypeStruct((Qp,), jnp.float32)] * 3
    q, mu, sd = pl.pallas_call(
        kernel,
        grid=(Qp // BQ,),
        in_specs=[pl.BlockSpec((BQ, W), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((BQ,), lambda i: (i,))] * 3,
        out_shape=out_shape,
        interpret=resolve_interpret(interpret),
    )(windows.astype(jnp.float32))
    return q[:Q], mu[:Q], sd[:Q]


# ---------------------------------------------------------------------------
# Fused time-batched fleet scan.
# ---------------------------------------------------------------------------

def monitor_fleet_kernel(comp_ref, m_ref, win_ref, fstate_ref, istate_ref,
                         qhist_ref, shist_ref, rhist_ref,
                         q_ref, qbar_ref, sig_ref, conv_ref, est_ref,
                         ep_ref, fout_ref, iout_ref, qhist_out_ref,
                         shist_out_ref, rhist_out_ref, *, P, t_len):
    """One queue block, time on sublanes and queues on lanes: (T, BQ)
    tiles, (k, BQ) histories and (1, BQ) per-queue rows."""
    fs = fstate_ref[...]                            # (N_FSTATE, BQ)
    ist = istate_ref[...]                           # (N_ISTATE, BQ)
    m = m_ref[...]                                  # (1, BQ) int32
    # Stage A parks the whole tile's quantiles in the q plane; Stage B
    # reads each row back and overwrites it with its ready-masked value
    q_ref[...] = fleet_window_stage(P, win_ref[...], comp_ref[...], axis=0)
    carry = (ist[0:1], fs[0:1], fs[1:2], fs[2:3], qhist_ref[...],
             shist_ref[...], rhist_ref[...], ist[1:2], fs[3:4])

    def body(t, carry):
        row = (pl.ds(t, 1), slice(None))
        carry, (qo, qb, sg, cv, es, ep) = fleet_step(
            P, carry, q_ref[row], t, m, axis=0)
        q_ref[row] = qo
        qbar_ref[row] = qb
        sig_ref[row] = sg
        conv_ref[row] = cv.astype(jnp.int32)
        est_ref[row] = es
        ep_ref[row] = ep
        return carry

    carry = jax.lax.fori_loop(0, t_len, body, carry)
    (s_fill, count, mean, m2, qhist, shist, rhist, epoch, last_qbar) = carry
    fout_ref[...] = jnp.concatenate(
        [count, mean, m2, last_qbar,
         jnp.zeros((N_FSTATE - 4,) + count.shape[1:], count.dtype)], axis=0)
    iout_ref[...] = jnp.concatenate(
        [s_fill, epoch,
         jnp.zeros((N_ISTATE - 2,) + s_fill.shape[1:], s_fill.dtype)],
        axis=0)
    qhist_out_ref[...] = qhist
    shist_out_ref[...] = shist
    rhist_out_ref[...] = rhist


@functools.partial(jax.jit, static_argnames=("cfg", "block_q", "interpret"))
def monitor_fleet_pallas(cfg: MonitorConfig, comp, m, win, fstate, istate,
                         qhist, shist, rhist, *, block_q: int = 256,
                         interpret: bool | None = None):
    """Launch the fused scan over a padded compacted tile, lane-major:
    comp (T, Qp), m (1, Qp), win (w, Qp), fstate (N_FSTATE, Qp), istate
    (N_ISTATE, Qp), qhist/rhist (conv_window, Qp), shist (2, Qp).

    Qp must be a multiple of the static ``block_q`` (ops.py pads and
    masks the tail); compiled for a device, ``block_q`` must also be a
    multiple of 128 (the lane width) or the whole of Qp.  Returns 6
    (T, Qp) per-step output planes + the 5 state arrays in the input
    layout.
    """
    T, Qp = comp.shape
    W = cfg.window
    CW = cfg.conv_window
    if Qp % block_q:
        raise ValueError(f"Q={Qp} not a multiple of block_q={block_q}")
    interpret = resolve_interpret(interpret)
    if not interpret and block_q % 128 and block_q != Qp:
        raise ValueError(
            f"block_q={block_q} must be a multiple of 128 (the TPU lane "
            f"width) or the whole padded queue axis Qp={Qp}")
    P = fleet_static_params(cfg)
    kernel = functools.partial(monitor_fleet_kernel, P=P, t_len=T)

    f32, i32 = jnp.float32, jnp.int32
    plane = lambda dt: jax.ShapeDtypeStruct((T, Qp), dt)   # noqa: E731
    rows = lambda n, dt: jax.ShapeDtypeStruct((n, Qp), dt)  # noqa: E731
    blk = lambda n: pl.BlockSpec((n, block_q), lambda i: (0, i))  # noqa: E731
    outs = pl.pallas_call(
        kernel,
        grid=(Qp // block_q,),
        in_specs=[blk(T), blk(1), blk(W), blk(N_FSTATE), blk(N_ISTATE),
                  blk(CW), blk(2), blk(CW)],
        out_specs=[blk(T)] * 6 + [blk(N_FSTATE), blk(N_ISTATE),
                                  blk(CW), blk(2), blk(CW)],
        out_shape=[plane(f32), plane(f32), plane(f32), plane(i32),
                   plane(f32), plane(i32), rows(N_FSTATE, f32),
                   rows(N_ISTATE, i32), rows(CW, f32), rows(2, f32),
                   rows(CW, f32)],
        interpret=interpret,
    )(comp.astype(f32), m.astype(i32), win.astype(f32),
      fstate.astype(f32), istate.astype(i32), qhist.astype(f32),
      shist.astype(f32), rhist.astype(f32))
    return outs
