"""Public op: full chunked SSD built on the Pallas intra-chunk kernel.

Matches ``repro.models.ssm.ssd_chunked`` (and therefore the sequential
``ssd_reference``) bit-for-bit up to float tolerance; the inter-chunk
state recurrence runs as a tiny ``lax.scan`` in JAX.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ssd.kernel import ssd_chunk_pallas
from repro.kernels.ssd.ref import ssd_chunk_ref

__all__ = ["ssd_chunked_pallas", "ssd_chunk_ref"]


def ssd_chunked_pallas(x, dt, A, Bm, Cm, chunk: int, *, h0=None,
                       interpret: bool | None = None):
    """x: (B,S,H,P) dt: (B,S,H) A: (H,) Bm/Cm: (B,S,N) -> (y, hT)."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    c = S // Q
    f32 = jnp.float32

    xc = x.reshape(B, c, Q, H, P)
    dtc = dt.reshape(B, c, Q, H)
    Bc = Bm.reshape(B, c, Q, N)
    Cc = Cm.reshape(B, c, Q, N)

    y_intra, sstate, decay = ssd_chunk_pallas(xc, dtc, A, Bc, Cc,
                                              interpret=interpret)

    def scan_fn(h_prev, inp):
        s_c, dec = inp
        return h_prev * dec[..., None, None] + s_c, h_prev

    if h0 is None:
        h0 = jnp.zeros((B, H, P, N), f32)
    hT, h_prevs = jax.lax.scan(
        scan_fn, h0, (jnp.moveaxis(sstate, 1, 0),
                      jnp.moveaxis(decay, 1, 0)))
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)          # (B,c,H,P,N)

    acum = jnp.cumsum(dtc.astype(f32) * A.astype(f32), axis=2)
    y_inter = jnp.einsum("bcqn,bcqh,bchpn->bcqhp",
                         Cc.astype(f32), jnp.exp(acum), h_prevs)
    return (y_intra + y_inter).reshape(B, S, H, P), hT
