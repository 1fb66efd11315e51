"""The reduction by phase and by span: on synthetic events, on a trace's
event metadata built here, on a trace recorded on a TPU v5e, and on one
recorded here."""

import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from harness import phases, tracefile
from harness.window import Tracer

DATA = Path(__file__).with_name("data")
DEV = "/device:TPU:0"
OPS, MODS = tracefile.OPS_LINES[0], tracefile.MODULES_LINE


def _synthetic():
    """A window [100, 1000) holding one whole execution of ``jit_step``
    [200, 500) and the start of another [900, 1100); an earlier one
    [0, 90) lies outside.  Times in ns."""
    dev = [
        (MODS, "jit_step(1)", 0, 90, ""),
        (OPS, "%a = f32[8]", 0, 90, "monitor.carry"),
        (MODS, "jit_step(1)", 200, 300, ""),
        (OPS, "%c = f32[8]", 200, 40, "monitor.compact"),
        (OPS, "%sort = s32[8]", 240, 60, ""),          # between compacts
        (OPS, "%f = f32[8]", 300, 20, "monitor.compact"),
        (OPS, "%w = f32[8]", 320, 30, "monitor.window"),
        (OPS, "%copy = f32[8]", 350, 10, ""),          # window | detect
        (OPS, "%d = f32[8]", 360, 50, "monitor.detect"),
        (OPS, "%g = f32[8]", 410, 100, "monitor.carry"),  # ends past 500
        ("Async XLA Ops", "%cs = f32[8]", 300, 150, ""),
        (MODS, "jit_pad(2)", 600, 50, ""),
        (OPS, "%p = f32[8]", 600, 50, ""),
        (MODS, "jit_step(1)", 900, 200, ""),
        (OPS, "%h = f32[8]", 900, 200, "monitor.carry"),
    ]
    host = [
        ("main", "bench.traced", 100, 900),
        ("main", "bench.call", 150, 500),
        ("main", "repro.monitor.stage", 160, 30),
        ("main", "repro.monitor.dispatch", 190, 300),
        ("main", "bench.gc", 200, 50),                 # inside dispatch
        ("main", "repro.monitor.unpad", 520, 100),
        ("main", "repro.monitor.stage", 950, 100),      # past the window
        ("other", "repro.fleet.collect", 195, 20),     # another thread
    ]
    events = [(DEV, line, n, float(t), float(d)) for line, n, t, d, _
              in dev]
    scopes = [sc for *_, sc in dev]
    events += [("host", line, n, float(t), float(d))
               for line, n, t, d in host]
    scopes += [""] * len(host)
    return events, scopes


def test_reduce_synthetic_events():
    r = phases.reduce(*_synthetic(), "jit_step")
    assert r["executions"] == 1
    s = {k: round(v * 1e9, 6) for k, v in r["scope_s"].items()}
    # the sort lies between two compaction ops; the copy between a
    # window op and a detect op stays unscoped; carry is clipped at 500
    assert s == {"monitor.compact": 120.0, "monitor.window": 30.0,
                 "monitor.detect": 50.0, "monitor.carry": 90.0}
    assert round(r["unscoped_s"] * 1e9, 6) == 10.0
    assert round(r["inferred_s"] * 1e9, 6) == 60.0
    assert round(r["compute_s"] * 1e9, 6) == 300.0
    span = {k: (round(v[0] * 1e9, 6), v[1])
            for k, v in r["span_s"].items()}
    assert span["repro.monitor.stage"] == (30.0, 1)
    assert span["repro.monitor.dispatch"] == (250.0, 1)   # less bench.gc
    assert span["repro.monitor.unpad"] == (100.0, 1)
    assert span["repro.fleet.collect"] == (20.0, 1)
    assert span["bench.call"] == (70.0, 1)
    gaps = dict(r["idle_gaps"])
    assert round(gaps["repro.monitor.unpad"] * 1e9, 6) == 80.0
    assert "other" in gaps


@pytest.mark.parametrize("ops,want", [
    (["a", "", "a"], [("a", False), ("a", True), ("a", False)]),
    (["a", "", "b"], [("a", False), ("", False), ("b", False)]),
    (["", "a", ""], [("", False), ("a", False), ("", False)]),
    (["a", "", "", "a", "", "b"],
     [("a", False), ("a", True), ("a", True), ("a", False), ("", False),
      ("b", False)]),
])
def test_bracket(ops, want):
    assert phases._bracket(ops) == want


def _pb(field, value):
    """One protobuf field: an int as a varint, bytes or str as a
    length-delimited value."""
    def varint(x):
        out = b""
        while True:
            out += bytes([(x & 0x7F) | (0x80 if x > 0x7F else 0)])
            x >>= 7
            if not x:
                return out
    if isinstance(value, int):
        return varint(field << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(field << 3 | 2) + varint(len(value)) + value


def test_op_names_read_from_event_metadata():
    stat_meta = (_pb(5, _pb(1, 7) + _pb(2, _pb(1, 7) + _pb(2, "tf_op")))
                 + _pb(5, _pb(1, 8) + _pb(2, _pb(1, 8) + _pb(
                     2, "jit(step)/monitor.detect/max:"))))

    def event(i, name, stat):
        return _pb(4, _pb(1, i) + _pb(2, _pb(1, i) + _pb(2, name)
                                      + _pb(4, name[:4]) + stat))
    device = (_pb(1, 3) + _pb(2, DEV) + stat_meta
              + event(1, "%f.1 = f32[8]",
                      _pb(5, _pb(1, 7)
                          + _pb(5, "jit(step)/monitor.carry/add:")))
              + event(2, "%m.2 = f32[8]",
                      _pb(5, _pb(1, 7) + _pb(7, 8)))       # interned
              + event(3, "%copy.3 = f32[8]", b"")
              + _pb(3, _pb(2, "XLA Ops")))
    host = _pb(2, "/host:CPU") + stat_meta + event(
        1, "%f.1 = f32[8]", _pb(5, _pb(1, 7) + _pb(5, "x/monitor.y")))
    ops = phases._op_names(_pb(1, device) + _pb(1, host))
    assert ops == {(DEV, "%f.1 = f32[8]"): "jit(step)/monitor.carry/add:",
                   (DEV, "%m.2 = f32[8]"): "jit(step)/monitor.detect/max:"}
    assert [phases.phase_of(v) for v in ops.values()] == [
        "monitor.carry", "monitor.detect"]


def test_reduce_recorded_v5e_phases():
    """Two 256-period ``run_monitor_fleet`` calls at 2e5 ends recorded on
    a TPU v5e (the trace cell's configuration and traffic): ``load``'s
    events, device op names cut to 120 characters, and its scopes."""
    with gzip.open(DATA / "trace_v5e_phases.json.gz", "rt") as f:
        rec = json.load(f)
    events = [tuple(e) for e in rec["events"]]
    r = phases.reduce(events, rec["scopes"], "jit_step")
    base = tracefile.reduce_events(events, "jit_step")
    assert r["executions"] == base["estimator_count"] == 2
    four = ("monitor.compact", "monitor.window", "monitor.detect",
            "monitor.carry")
    assert set(r["scope_s"]) == set(four)
    parts = sum(r["scope_s"].values()) + r["unscoped_s"]
    assert parts == pytest.approx(r["compute_s"], rel=1e-12)
    # the compute line fills the dispatch program's executions
    assert r["compute_s"] == pytest.approx(base["estimator_s"], rel=1e-4)
    assert r["unscoped_s"] < 0.05 * r["compute_s"]
    m = phases.metrics(r)
    assert sum(m[f"{p.split('.')[1]}_device_ms"] for p in four) \
        + m["unscoped_device_ms"] == pytest.approx(
            base["estimator_s"] / 2 * 1e3, rel=1e-2)
    assert {n: c for n, (_, c) in r["span_s"].items()
            if n.startswith("repro.")} == {
        f"repro.monitor.{s}": 2 for s in ("stage", "pad", "dispatch",
                                          "unpad")}
    assert m["stage_ms"] > 0
    assert any(n.startswith("repro.monitor.") for n, _ in r["idle_gaps"])
    gaps = sum(s for _, s in r["idle_gaps"])
    assert gaps == pytest.approx(base["window_s"] - base["busy_s"],
                                 abs=1e-9)


def test_load_reads_the_program_spans():
    import jax

    from repro.core.monitor import MonitorConfig, run_monitor_fleet

    cfg = MonitorConfig()
    rng = np.random.default_rng(0)
    tc = rng.poisson(100.0, (40, 64)).astype(np.float32)
    blk = rng.random((40, 64)) < 0.1
    jax.block_until_ready(run_monitor_fleet(cfg, tc, blk, chunk_t=32,
                                            mode="state", block_q=64))
    tr = Tracer(True)
    try:
        tr.start()
        with tr.span("bench.call"):
            st, _ = run_monitor_fleet(cfg, tc, blk, chunk_t=32,
                                      mode="state", block_q=64)
            jax.block_until_ready(st)
        tr.stop()
        r = phases.reduce_dir(tr.dir, "jit_step")
    finally:
        tr.close()
    assert {f"repro.monitor.{s}" for s in ("stage", "pad", "dispatch",
                                           "unpad")} <= set(r["span_s"])
    assert r["span_s"]["repro.monitor.dispatch"][1] == 1


@pytest.fixture(scope="module")
def small_spec(tmp_path_factory):
    """Every configuration file at 48 links as a cell on the CPU."""
    root = Path(tracefile.__file__).resolve().parents[2]
    d = tmp_path_factory.mktemp("spec")
    spec = {"configs": [], "workloads": [], "end_to_end": [],
            "per_layer": []}
    for f in sorted((root / "bench" / "configs").glob("*.json")):
        cfg = json.loads(f.read_text())
        cfg.update(n_links=48, ref_ends=96)
        (d / f.name).write_text(json.dumps(cfg))
        spec["configs"].append({"name": f.stem, "file": str(d / f.name)})
        spec["workloads"].append({"name": f"{f.stem}.dual_phase",
                                  "config": f.stem,
                                  "traffic": "dual_phase", "chips": 1})
    path = d / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return path


@pytest.mark.parametrize("cell", ["tandem_fleet_1e5.dual_phase",
                                  "tandem_trace_2e5.dual_phase"])
def test_phase_run(small_spec, cell, capsys):
    import phase_run

    rc = phase_run.main(["--workload", cell, "--seed", "3000000019",
                   "--seconds", "1", "--spec", str(small_spec)],
                  allow_cpu=True)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"]
    assert out["phases"]["stage_ms"] > 0
    spans = set(out["span_s"])
    assert {"repro.monitor.stage", "repro.monitor.dispatch"} <= spans
    if cell.startswith("tandem_fleet"):
        assert {f"repro.fleet.{s}" for s in (
            "collect", "harvest", "slo", "transpose", "classify",
            "estimate")} <= spans
