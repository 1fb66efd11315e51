"""Pallas TPU kernel: Mamba-2 SSD intra-chunk compute.

Grid = (batch, n_chunks, heads).  Per program: one (Q, P) head-chunk plus
the shared (Q, N) B/C projections live in VMEM; the (Q, Q) masked decay
matmul pair runs on the MXU.  Q=chunk (<=256), P=head dim (64), N=state
(64-128) — with Q=256, N=128, P=64 the working set is
~(3*Q*N + Q*P + Q*Q)*4B ~ 720 KB, comfortably inside VMEM, and both
matmuls are 128-aligned.

The inter-chunk state scan is sequential and tiny; it stays in JAX
(``ops.ssd_chunked_pallas``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.backend import resolve_interpret

__all__ = ["ssd_chunk_kernel", "ssd_chunk_pallas"]


def ssd_chunk_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref,
                     y_ref, state_ref, decay_ref):
    x = x_ref[0, 0, 0].astype(jnp.float32)        # (Q, P)
    dt = dt_ref[0, 0, :, 0].astype(jnp.float32)   # (Q,)
    A = a_ref[0].astype(jnp.float32)              # ()
    Bm = b_ref[0, 0].astype(jnp.float32)          # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)          # (Q, N)
    Q = x.shape[0]

    a = dt * A                                    # (Q,)
    acum = jnp.cumsum(a)                          # (Q,)
    CB = jnp.dot(Cm, Bm.T, preferred_element_type=jnp.float32)  # (Q,Q) MXU
    diff = acum[:, None] - acum[None, :]
    mask = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    M = jnp.where(mask, CB * jnp.exp(diff), 0.0) * dt[None, :]
    y = jnp.dot(M, x, preferred_element_type=jnp.float32)       # (Q,P) MXU

    dte = jnp.exp(acum[-1] - acum)                # (Q,)
    xw = x * (dt * dte)[:, None]                  # (Q,P)
    state = jnp.dot(xw.T, Bm, preferred_element_type=jnp.float32)  # (P,N)

    y_ref[0, 0, 0] = y
    state_ref[0, 0, 0] = state
    decay_ref[0, 0, 0] = jnp.exp(acum[-1])


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk_pallas(x, dt, A, Bm, Cm, *, interpret: bool | None = None):
    """Batched intra-chunk SSD.

    x: (B,c,Q,H,P) dt: (B,c,Q,H) A: (H,) Bm/Cm: (B,c,Q,N)
    -> (y_intra (B,c,Q,H,P), sstate (B,c,H,P,N), decay (B,c,H))
    """
    B, c, Q, H, P = x.shape
    N = Bm.shape[-1]
    xt = jnp.moveaxis(x, 3, 2)                    # (B,c,H,Q,P)
    f32 = jnp.float32

    grid = (B, c, H)
    y, state, decay = pl.pallas_call(
        ssd_chunk_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda b, k, h: (b, k, h, 0, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda b, k, h: (b, k, 0, h)),
            pl.BlockSpec((1,), lambda b, k, h: (h,)),
            pl.BlockSpec((1, 1, Q, N), lambda b, k, h: (b, k, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, k, h: (b, k, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda b, k, h: (b, k, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, P, N), lambda b, k, h: (b, k, h, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, k, h: (b, k, h)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, c, H, Q, P), f32),
            jax.ShapeDtypeStruct((B, c, H, P, N), f32),
            jax.ShapeDtypeStruct((B, c, H), f32),
        ],
        interpret=resolve_interpret(interpret),
    )(xt.astype(f32), dt.astype(f32), A.astype(f32),
      Bm.astype(f32), Cm.astype(f32))
    return jnp.moveaxis(y, 2, 3), state, decay
