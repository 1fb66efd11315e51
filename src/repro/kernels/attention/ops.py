"""Public op: flash attention with automatic fallback.

With ``use_pallas`` this is the fused Pallas kernel (compiled on a TPU,
interpreted on the CPU backend — ``core.backend.resolve_interpret``);
otherwise the jnp reference keeps semantics identical.  Used by the
serving path for long prefills.
"""

from __future__ import annotations

import jax

from repro.kernels.attention.kernel import flash_attention_pallas
from repro.kernels.attention.ref import attention_ref

__all__ = ["flash_attention", "attention_ref"]


def flash_attention(q, k, v, *, causal: bool = True,
                    use_pallas: bool = True,
                    interpret: bool | None = None):
    if use_pallas:
        return flash_attention_pallas(q, k, v, causal=causal,
                                      interpret=interpret)
    return attention_ref(q, k, v, causal=causal)
