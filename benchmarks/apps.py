"""Figs 16/17: the paper's two full applications — streaming matrix
multiply and Rabin-Karp string search — on our instrumented pipeline.

``matmul_pipeline`` / ``rabin_karp_pipeline`` build each application
and its correctness check; ``fig16_matmul_app`` / ``fig17_rabin_karp``
run them as benchmark rows."""

from __future__ import annotations

import time

import numpy as np

from repro.core.monitor import MonitorConfig
from repro.streams import Pipeline, Stage


def matmul_pipeline(n: int = 256):
    """Streaming dense matmul: reader -> 4 dot-product kernels -> reduce.
    The reduce kernel's queue is instrumented (as in the paper).
    Returns ``(pipe, correct)``; ``correct()`` compares the reduced rows
    with ``A @ B`` once the pipeline has drained."""
    A = np.random.default_rng(0).normal(size=(n, n)).astype(np.float32)
    B = np.random.default_rng(1).normal(size=(n, n)).astype(np.float32)

    def rows():
        for i in range(n):
            yield (i, A[i])

    def dot(item):
        i, row = item
        return (i, row @ B)

    acc = np.zeros((n, n), np.float32)

    def reduce(item):
        i, r = item
        acc[i] = r
        return item

    pipe = Pipeline([Stage("read", source=rows()),
                     Stage("dot", fn=dot, replicas=4),
                     Stage("reduce", fn=reduce)],
                    capacity=32, base_period_s=2e-3,
                    monitor_cfg=MonitorConfig(window=16, min_q_samples=16))
    return pipe, lambda: bool(np.allclose(acc, A @ B, atol=1e-3))


def rabin_karp_pipeline(reps: int = 200_000):
    """Rabin-Karp over a 'foobar' * ``reps`` corpus; the hash kernel's
    out-queue is instrumented (paper: low-rho, hard-to-observe case).
    Returns ``(pipe, expected)``: the pipeline's output is one list of
    verified match offsets per chunk, ``expected`` matches in all."""
    corpus = b"foobar" * reps
    pattern = b"foobar"
    m = len(pattern)
    q = (1 << 31) - 1
    base = 256
    h_pat = 0
    for c in pattern:
        h_pat = (h_pat * base + c) % q
    chunk_len = 4096

    def chunks():
        for off in range(0, len(corpus) - m + 1, chunk_len):
            yield (off, corpus[off:off + chunk_len + m - 1])

    def rolling_hash(item):
        off, text = item
        hits = []
        h = 0
        hi = pow(base, m - 1, q)
        for i, c in enumerate(text):
            h = (h * base + c) % q
            if i >= m - 1:
                if h == h_pat:
                    hits.append(off + i - m + 1)
                h = (h - text[i - m + 1] * hi) % q
        return (off, text, hits)

    def verify(item):
        off, text, hits = item
        real = [p for p in hits
                if corpus[p:p + m] == pattern]
        return real

    pipe = Pipeline([Stage("read", source=chunks()),
                     Stage("hash", fn=rolling_hash, replicas=4),
                     Stage("verify", fn=verify, replicas=2)],
                    capacity=32, base_period_s=2e-3,
                    monitor_cfg=MonitorConfig(window=16, min_q_samples=16))
    return pipe, len(corpus) // m


def fig16_matmul_app():
    pipe, correct = matmul_pipeline()
    t0 = time.perf_counter()
    out = pipe.run_collect(timeout_s=120)
    dt = time.perf_counter() - t0
    ok = correct()
    rates = pipe.rates()
    reduce_rate = rates["dot->reduce"]["service_rate"]
    return ([f"fig16_matmul,{dt * 1e6:.0f},rows={len(out)}_correct={ok}"
             f"_reduce_rate={reduce_rate:.0f}/s"],
            f"matmul correct={ok}; instrumented reduce kernel rate "
            f"{reduce_rate:.0f} rows/s (paper Fig 16 instruments reduce)")


def fig17_rabin_karp():
    pipe, expect = rabin_karp_pipeline()
    t0 = time.perf_counter()
    out = pipe.run_collect(timeout_s=180)
    dt = time.perf_counter() - t0
    n_matches = sum(len(x) for x in out)
    rates = pipe.rates()
    vq = rates["hash->verify"]
    return ([f"fig17_rabin_karp,{dt * 1e6:.0f},matches={n_matches}"
             f"_expected~{expect}_verify_rate={vq['service_rate']:.0f}"
             f"_blockfrac={vq['blocking_frac']:.2f}"],
            f"found {n_matches}/{expect} matches; verify-queue blocking "
            f"fraction {vq['blocking_frac']:.2f} (paper: low-rho queue is "
            "the hard case)")


ALL = [fig16_matmul_app, fig17_rabin_karp]
