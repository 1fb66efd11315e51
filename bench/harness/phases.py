"""Device time by the estimator's phase, and host time by program span.

The program names the phases of its fleet step with ``jax.named_scope``
(``monitor.compact``, ``monitor.window``, ``monitor.detect``,
``monitor.carry``; ``monitor.layout`` and ``monitor.pallas`` in the
Pallas form) and its host stages with profiler spans named
``repro.<layer>.<stage>``.  ``load`` reads a trace as ``tracefile.load``
does, keeping the host spans named ``repro.*`` beside ``bench.*``, and
returns beside the events a list of the same length that holds each
compute operation's phase: the ``monitor.*`` part of its HLO
``op_name``, else ``""``.  On a TPU the op_name is the ``tf_op`` stat of
the operation's event metadata, which ``jax.profiler.ProfileData`` does
not expose, so ``_op_names`` reads it from the ``.xplane.pb`` itself.

``reduce`` then computes, over the traced window:

* ``scope_s``: device seconds per phase, on the compute line
  (``XLA Ops``) only, inside the whole executions of the estimator's
  dispatch program (``tracefile``'s rule for ``estimator_s``), each
  operation clipped to its execution.  Operations that XLA creates
  while lowering carry no op_name (on a v5e the compaction's scatter
  becomes a ``sort`` and a custom fusion without one); such an operation
  takes the phase of the named operations that run just before and
  just after it, when those two agree (``inferred_s`` sums them);
* ``unscoped_s``: the compute time inside those executions that no
  phase claims; ``compute_s`` all of it, ``executions`` their number;
* ``span_s``: for each host span (``repro.*`` and the harness's
  ``bench.*``) that lies wholly inside the window, ``[self seconds,
  count]``; self time is the span's duration less that of the spans
  directly inside it on the same thread;
* ``idle_gaps``: ``tracefile``'s idle gaps, now also attributed to the
  innermost ``repro.*`` span that covers them.

``metrics`` turns the result into per-execution numbers.
"""

from __future__ import annotations

import glob
import os

from harness import tracefile

__all__ = ["load", "reduce", "reduce_dir", "metrics", "phase_of",
           "PHASES"]

PHASES = ("monitor.compact", "monitor.window", "monitor.detect",
          "monitor.carry", "monitor.layout", "monitor.pallas")
COMPUTE_LINE = tracefile.OPS_LINES[0]
HOST_PREFIXES = ("bench.", "repro.")


def phase_of(op_name: str) -> str:
    for part in op_name.split("/"):
        if part.startswith("monitor."):
            return part
    return ""


# -- the event metadata of an XSpace protobuf (xplane.proto: XSpace.planes
# 1; XPlane.name 2, event_metadata 4, stat_metadata 5; map entries key 1,
# value 2; XEventMetadata.name 2, stats 5; XStatMetadata.name 2;
# XStat.metadata_id 1, str_value 5, ref_value 7) --------------------------

def _varint(b, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b):
    """(field number, value) of one message: ints for varints, bytes
    views for length-delimited fields."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _map_values(b) -> list:
    return [v for f, v in _fields(b) if f == 2]


def _op_names(data: bytes) -> dict:
    """{(device plane, event name): op_name} for every event metadata of
    a device plane that carries a ``tf_op`` stat."""
    out = {}
    for f, plane in _fields(memoryview(data)):
        if f != 1:
            continue
        name, metas, stat_names = "", [], {}
        for g, v in _fields(plane):
            if g == 2:
                name = bytes(v).decode()
            elif g == 4:
                metas += _map_values(v)
            elif g == 5:
                for sm in map(dict, map(_fields, _map_values(v))):
                    stat_names[sm.get(1, 0)] = bytes(sm.get(2, b"")).decode()
        if not name.startswith("/device:"):
            continue
        tf_op = [k for k, s in stat_names.items() if s == "tf_op"]
        if not tf_op:
            continue
        for meta in metas:
            ev_name, op = "", ""
            for g, v in _fields(meta):
                if g == 2:
                    ev_name = bytes(v).decode()
                elif g == 5:
                    st = dict(_fields(v))
                    if st.get(1) == tf_op[0]:
                        op = (bytes(st[5]).decode() if 5 in st
                              else stat_names.get(st.get(7), ""))
            if op:
                out[(name, ev_name)] = op
    return out


def load(trace_dir: str) -> tuple[list, list]:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    events, scopes = [], []
    for path in paths:
        with open(path, "rb") as f:
            data = f.read()
        ops = _op_names(data)
        pd = ProfileData.from_serialized_xspace(data)
        for plane in pd.planes:
            if plane.name.startswith("/device:"):
                kind = plane.name
            elif plane.name.startswith("/host:"):
                kind = "host"
            else:
                continue
            for line in plane.lines:
                if kind != "host" and line.name not in (
                        *tracefile.OPS_LINES, tracefile.MODULES_LINE):
                    continue
                for ev in line.events:
                    if kind == "host" and not ev.name.startswith(
                            HOST_PREFIXES):
                        continue
                    events.append((kind, line.name, ev.name,
                                   float(ev.start_ns),
                                   float(ev.duration_ns)))
                    scopes.append(phase_of(ops.get((kind, ev.name), ""))
                                  if line.name == COMPUTE_LINE else "")
    return events, scopes


def _window(events: list) -> tuple[float, float]:
    win = [(t, t + d) for k, _, n, t, d in events
           if k == "host" and n == tracefile.WINDOW_SPAN]
    if win:
        return min(a for a, _ in win), max(b for _, b in win)
    ts = [(t, t + d) for k, _, _, t, d in events if k != "host"]
    return ((min(a for a, _ in ts), max(b for _, b in ts)) if ts
            else (0.0, 0.0))


def _bracket(ops: list) -> list:
    """Phases of one execution's operations (in start order), each
    unnamed one given the phase of its nearest named neighbours when
    the one before and the one after agree; ``(phase, inferred)``."""
    before, last = [], ""
    for sc in ops:
        last = sc or last
        before.append(last)
    out, nxt = [], ""
    for sc, prev in zip(reversed(ops), reversed(before)):
        nxt = sc or nxt
        if sc:
            out.append((sc, False))
        else:
            out.append((prev, True) if prev and prev == nxt else ("", False))
    return out[::-1]


def _self_times(events: list, lo: float, hi: float) -> dict:
    """Self time and count of every host span wholly inside [lo, hi].
    Spans on one thread nest, so a stack finds each span's direct
    parent."""
    by_line: dict = {}
    for k, line, n, t, d in events:
        if (k == "host" and n != tracefile.WINDOW_SPAN
                and lo <= t and t + d <= hi):
            by_line.setdefault(line, []).append((t, -(t + d), n))
    out: dict = {}
    for spans in by_line.values():
        spans.sort()
        stack, done = [], []    # [end, name, duration, children's time]
        for t, neg_end, n in spans:
            end = -neg_end
            while stack and stack[-1][0] <= t:
                done.append(stack.pop())
            if stack:
                stack[-1][3] += end - t
            stack.append([end, n, end - t, 0.0])
        for _, n, dur, kids in done + stack:
            s = out.setdefault(n, [0.0, 0])
            s[0] += (dur - kids) * 1e-9
            s[1] += 1
    return out


def reduce(events: list, scopes: list, module_prefix: str) -> dict:
    lo, hi = _window(events)
    devices = sorted({k for k, line, *_ in events
                      if k != "host" and line in tracefile.OPS_LINES})
    scope_s: dict = {}
    inferred = n_exec = 0.0
    for dev in devices:
        runs = sorted((t, t + d) for k, line, n, t, d in events
                      if k == dev and line == tracefile.MODULES_LINE
                      and n.startswith(module_prefix)
                      and lo <= t < hi and t + d <= hi)
        n_exec += len(runs)
        ops = sorted((t, t + d, sc) for (k, line, _, t, d), sc
                     in zip(events, scopes)
                     if k == dev and line == COMPUTE_LINE)
        for a, b in runs:
            inside = [(max(t, a), min(e, b), sc) for t, e, sc in ops
                      if t < b and e > a]
            for (t, e, _), (sc, guess) in zip(
                    inside, _bracket([sc for *_, sc in inside])):
                scope_s[sc] = scope_s.get(sc, 0.0) + (e - t) * 1e-9
                inferred += (e - t) * 1e-9 if guess else 0.0
    nd = max(len(devices), 1)
    unscoped = scope_s.pop("", 0.0)
    return {
        "scope_s": {k: v / nd for k, v in sorted(scope_s.items())},
        "unscoped_s": unscoped / nd,
        "inferred_s": inferred / nd,
        "compute_s": (unscoped + sum(scope_s.values())) / nd,
        "executions": n_exec / nd,
        "span_s": _self_times(events, lo, hi),
        "idle_gaps": tracefile.reduce_events(events,
                                             module_prefix)["idle_gaps"],
    }


def reduce_dir(trace_dir: str, module_prefix: str) -> dict:
    return reduce(*load(trace_dir), module_prefix)


def metrics(r: dict) -> dict:
    """Per execution of the dispatch program: each phase's device time
    (``compact_device_ms`` ...), the unscoped remainder
    (``unscoped_device_ms``) and the whole compute line
    (``compute_device_ms``); and the host self time per call of
    ``repro.monitor.stage`` (``stage_ms``)."""
    out = {}
    if r["executions"]:
        per = 1e3 / r["executions"]
        for name in PHASES:
            if name in r["scope_s"]:
                out[f"{name.split('.')[1]}_device_ms"] = \
                    r["scope_s"][name] * per
        out["unscoped_device_ms"] = r["unscoped_s"] * per
        out["compute_device_ms"] = r["compute_s"] * per
    stage = r["span_s"].get("repro.monitor.stage")
    if stage and stage[1]:
        out["stage_ms"] = stage[0] / stage[1] * 1e3
    return out
