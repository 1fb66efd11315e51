"""What a measured window needs besides the system: a meter for the time
the interpreter spends in garbage collection, and the profiler with the
harness's own spans around the public calls it makes."""

from __future__ import annotations

import contextlib
import gc
import shutil
import tempfile
import time

__all__ = ["GcMeter", "Tracer"]

_NULL = contextlib.nullcontext()


class GcMeter:
    """Seconds of garbage collection between ``start()`` and ``stop()``,
    taken from ``gc.callbacks`` (collections are not switched off).
    While a tracer is active each collection is also a ``bench.gc``
    span in the profiler's trace."""

    def __init__(self, tracer: "Tracer | None" = None):
        self.seconds = 0.0
        self.collections = [0, 0, 0]
        self._t = 0.0
        self._on = False
        self._tracer = tracer
        self._span = None

    def _cb(self, phase, info):
        if not self._on:
            return
        if phase == "start":
            self._t = time.perf_counter()
            if self._tracer is not None and self._tracer.active:
                self._span = self._tracer.span("bench.gc")
                self._span.__enter__()
        else:
            self.seconds += time.perf_counter() - self._t
            self.collections[info["generation"]] += 1
            if self._span is not None:
                self._span.__exit__(None, None, None)
                self._span = None

    def start(self):
        gc.callbacks.append(self._cb)
        self._on = True

    def stop(self):
        self._on = False
        if self._cb in gc.callbacks:
            gc.callbacks.remove(self._cb)


class Tracer:
    """The JAX profiler over part of a window, written to a temporary
    directory that ``close()`` removes.  ``span(name)`` is a profiler
    annotation while the trace runs and costs nothing otherwise."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.active = False
        self.dir = None
        self._window = None

    def span(self, name: str):
        if not self.active:
            return _NULL
        import jax
        return jax.profiler.TraceAnnotation(name)

    def start(self):
        if not self.enabled or self.active:
            return
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.active = True
        self._window = jax.profiler.TraceAnnotation("bench.traced")
        self._window.__enter__()

    def stop(self):
        if not self.active:
            return
        import jax
        self._window.__exit__(None, None, None)
        self.active = False
        jax.profiler.stop_trace()

    def close(self):
        if self.dir is not None:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir = None
