"""From the profiler's trace to the device metrics.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
flat list of events (plane kind, line, name, start, duration in ns).
``reduce_events`` then computes, over the traced window (the harness's
``bench.traced`` span):

* ``busy_s``: the union of the intervals in which an operation ran on a
  device, averaged over the devices that ran any; ``window_s`` the
  window's length;
* ``estimator_s`` / ``estimator_count``: the summed device time and the
  number of executions of the estimator's dispatch program (the XLA
  module whose name starts with the configured prefix) that ran wholly
  inside the window;
* ``device_ops``: the ten operations that took most device time;
* ``idle_gaps``: the device's idle time inside the window, attributed to
  what the host was doing (the innermost ``bench.*`` span covering it,
  else ``other``), the ten largest.
"""

from __future__ import annotations

import glob
import os

import numpy as np

__all__ = ["load", "reduce_events", "reduce_dir", "OPS_LINES",
           "MODULES_LINE", "WINDOW_SPAN"]

OPS_LINES = ("XLA Ops", "Async XLA Ops")   # compute and its DMA
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.traced"


def load(trace_dir: str) -> list:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    events = []
    for path in paths:
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            if plane.name.startswith("/device:"):
                kind = plane.name
            elif plane.name.startswith("/host:"):
                kind = "host"
            else:
                continue
            for line in plane.lines:
                if kind != "host" and line.name not in (*OPS_LINES,
                                                        MODULES_LINE):
                    continue
                for ev in line.events:
                    if kind == "host" and not ev.name.startswith("bench."):
                        continue
                    events.append((kind, line.name, ev.name,
                                   float(ev.start_ns),
                                   float(ev.duration_ns)))
    return events


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge (n, 2) [start, end) intervals into disjoint sorted ones."""
    if iv.size == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for a, b in iv[1:]:
        if a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return np.array(out)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def _top(totals: dict, n: int = 10) -> list:
    items = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, float(sec)] for name, sec in items]


def _attribute(gaps: np.ndarray, spans: list, out: dict) -> None:
    """Add each gap's time to the innermost (latest-opened) span that
    covers it, or to ``other``: one sweep over all the boundaries."""
    marks = [(a, 1, -1) for a, _ in gaps] + [(b, 0, -1) for _, b in gaps]
    marks += [(a, 3, i) for i, (_, a, _) in enumerate(spans)]
    marks += [(b, 2, i) for i, (_, _, b) in enumerate(spans)]
    marks.sort()
    in_gap, active, prev = False, [], None
    for x, kind, i in marks:
        if prev is not None and in_gap and x > prev:
            name = spans[max(active, key=lambda j: spans[j][1])][0] \
                if active else "other"
            out[name] = out.get(name, 0.0) + (x - prev) * 1e-9
        prev = x
        if kind == 1:
            in_gap = True
        elif kind == 0:
            in_gap = False
        elif kind == 3:
            active.append(i)
        elif i in active:
            active.remove(i)


def reduce_events(events: list, module_prefix: str) -> dict:
    host = [(n, t, t + d) for k, _, n, t, d in events if k == "host"]
    win = [(t0, t1) for n, t0, t1 in host if n == WINDOW_SPAN]
    # the chips the run used: those on which an operation ran
    devices = sorted({k for k, line, *_ in events
                      if k != "host" and line in OPS_LINES})
    if win:
        lo, hi = min(w[0] for w in win), max(w[1] for w in win)
    else:
        ts = [(t, t + d) for k, _, _, t, d in events if k != "host"]
        lo = min(a for a, _ in ts) if ts else 0.0
        hi = max(b for _, b in ts) if ts else 0.0
    spans = [(n, a, b) for n, a, b in host
             if n != WINDOW_SPAN and b > lo and a < hi]

    busy, est_s, est_n = [], 0.0, 0
    op_time: dict = {}
    gap_time: dict = {}
    for dev in devices:
        ops = np.array([(t, t + d) for k, line, _, t, d in events
                        if k == dev and line in OPS_LINES]).reshape(-1, 2)
        merged = _clip(_union(ops), lo, hi)
        busy.append(float(np.sum(merged[:, 1] - merged[:, 0])) * 1e-9)
        for k, line, name, t, d in events:
            if k != dev or not lo <= t < hi:
                continue
            if line in OPS_LINES:
                op = name.split(" = ")[0] + " " + name.split(" = ")[-1][:48]
                op_time[op] = op_time.get(op, 0.0) + d * 1e-9
            elif (line == MODULES_LINE and name.startswith(module_prefix)
                  and t + d <= hi):        # whole executions only
                est_s += d * 1e-9
                est_n += 1
        # idle gaps inside the window, by the innermost covering span
        gaps = np.concatenate([[lo], merged.ravel(), [hi]]).reshape(-1, 2)
        _attribute(gaps[gaps[:, 1] > gaps[:, 0]], spans, gap_time)
    nd = max(len(devices), 1)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": float(np.mean(busy)) if busy else 0.0,
        "estimator_s": est_s / nd,
        "estimator_count": est_n / nd,
        "device_ops": _top({k: v / nd for k, v in op_time.items()}),
        "idle_gaps": _top({k: v / nd for k, v in gap_time.items()}),
    }


def reduce_dir(trace_dir: str, module_prefix: str) -> dict:
    return reduce_events(load(trace_dir), module_prefix)
