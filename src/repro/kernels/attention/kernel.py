"""Pallas TPU kernel: blocked causal flash attention (forward).

Grid = (batch, q-head, Sq/BQ).  Each program streams KV blocks of BK rows
through VMEM with an online-softmax accumulator — the S x T score matrix
never exists in HBM, which is what makes the 32k prefill shapes fit
(DESIGN.md section 6).  BQ/BK default to 128 to align the MXU.

Forward only: serving (prefill/decode) path.  Training keeps the XLA
einsum attention (with remat) so autodiff stays source-of-truth.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.backend import resolve_interpret

__all__ = ["flash_attention_pallas"]

_NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, bq, bk, t_len, scale,
                  causal):
    qi = pl.program_id(2)
    q = q_ref[0, :, 0, :].astype(jnp.float32) * scale       # (BQ, hd)
    acc = jnp.zeros((bq, q.shape[-1]), jnp.float32)
    m = jnp.full((bq,), _NEG, jnp.float32)
    l = jnp.zeros((bq,), jnp.float32)

    n_kv = t_len // bk

    def body(j, carry):
        acc, m, l = carry
        kv = (0, pl.ds(j * bk, bk), 0, slice(None))
        k = k_ref[kv].astype(jnp.float32)
        v = v_ref[kv].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (BQ,BK)
        if causal:
            q_idx = qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            k_idx = j * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(q_idx >= k_idx, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    if causal:
        # only kv blocks at or before this q block contribute
        n_iter = jnp.minimum((qi + 1) * bq, t_len) // bk
        n_iter = jnp.maximum(n_iter, 1)
    else:
        n_iter = n_kv
    acc, m, l = jax.lax.fori_loop(0, n_iter, body, (acc, m, l))
    o_ref[0, :, 0, :] = acc / jnp.maximum(l, 1e-30)[:, None]


@functools.partial(jax.jit, static_argnames=("causal", "block_q",
                                             "block_k", "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool | None = None):
    """q: (B,S,H,hd) k/v: (B,T,K,hd) GQA -> (B,S,H,hd) float32."""
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    BQ = min(block_q, S)
    BK = min(block_k, T)
    assert S % BQ == 0 and T % BK == 0, (S, BQ, T, BK)
    scale = hd ** -0.5

    kernel = functools.partial(_flash_kernel, bq=BQ, bk=BK, t_len=T,
                               scale=scale, causal=causal)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, S // BQ),
        in_specs=[
            pl.BlockSpec((1, BQ, 1, hd), lambda b, h, i: (b, i, h, 0)),
            # whole KV stream for this program's kv-head in VMEM window
            pl.BlockSpec((1, T, 1, hd),
                         lambda b, h, i, _G=G: (b, 0, h // _G, 0)),
            pl.BlockSpec((1, T, 1, hd),
                         lambda b, h, i, _G=G: (b, 0, h // _G, 0)),
        ],
        out_specs=pl.BlockSpec((1, BQ, 1, hd),
                               lambda b, h, i: (b, i, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, H, hd), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(q, k, v)
    return out
