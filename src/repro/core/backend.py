"""Host-or-device selection and the persistent compile-cache location.

``on_host()`` is the one backend probe: every "run this on the host or
on the device" choice in the repo goes through it — the Pallas
``interpret`` default (``resolve_interpret``) and the control decision's
``impl="auto"`` (``control.policy.resolve_impl``).  On the CPU backend
the kernels run in the Pallas interpreter and the decision runs as
numpy; on a TPU the kernels compile through Mosaic and the decision is
a jitted dispatch.

``enable_compile_cache()`` is for entry points only (``chip_smoke.py``,
``benchmarks/run.py``); nothing calls it at import time.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

__all__ = ["on_host", "resolve_interpret", "compile_cache_dir",
           "enable_compile_cache", "CHECKOUT"]

# the repository checkout this package was loaded from (src/repro/core/..)
CHECKOUT = Path(__file__).resolve().parents[3]
_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def on_host() -> bool:
    """True when JAX's default backend is the CPU."""
    return jax.default_backend() == "cpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """A kernel entry point's ``interpret`` flag: ``None`` means the
    Pallas interpreter on the CPU backend and a compiled kernel on a
    device; an explicit bool is honoured as given."""
    return on_host() if interpret is None else bool(interpret)


def compile_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else the fixed
    ``<checkout>/.jax_cache`` (a fixed path: the directory is part of
    the cache key, so a path that moves never hits)."""
    return os.environ.get(_CACHE_ENV) or str(CHECKOUT / ".jax_cache")


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache for this process, at
    ``compile_cache_dir()``.  When ``$JAX_COMPILATION_CACHE_DIR`` is set
    JAX reads it itself and nothing is set here."""
    if not os.environ.get(_CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
