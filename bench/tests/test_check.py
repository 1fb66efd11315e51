"""The whole run, at a size the CPU holds, with the chip look skipped:
a sound program is correct; the lower-precision control, whole and in
its two parts, and each fault the cells can have (the estimator
returning its state unchanged, half of the ends left out, the estimates
altered where they are produced, on every end or on one tile of rows)
are not.  The exchange between chips does not exist on one chip.  The
live fleet's configuration, whose cell is not in ``BENCHMARK.json``
yet, runs here as ``tandem_fleet_1e5.dual_phase`` all the same."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
from harness import check
from repro.core import monitor
from repro.streams import fleet

ROOT = Path(run.__file__).resolve().parents[1]
CELLS = ("tandem_fleet_1e5.dual_phase", "tandem_trace_2e5.dual_phase")
REAL = monitor.run_monitor_fleet


@pytest.fixture(scope="module")
def small_spec(tmp_path_factory):
    """The benchmark as committed, at 48 links on the one CPU device,
    with a cell for every configuration file."""
    d = tmp_path_factory.mktemp("spec")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    named = {c["name"] for c in spec["configs"]}
    for f in sorted((ROOT / "bench" / "configs").glob("*.json")):
        if f.stem not in named:
            spec["configs"].append({"name": f.stem,
                                    "file": str(f.relative_to(ROOT))})
            spec["workloads"].append({"name": f"{f.stem}.dual_phase",
                                      "config": f.stem,
                                      "traffic": "dual_phase"})
    for w in spec["workloads"]:
        w["chips"] = 1
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cfg.update(n_links=48, ref_ends=96)
        p = d / f"{c['name']}.json"
        p.write_text(json.dumps(cfg))
        c["file"] = str(p)
    path = d / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return path


def _run(spec, cell, capsys, control=0):
    rc = run.main(["--workload", cell, "--seed", "3000000019", "--seconds",
                   "1", "--trace", "0", "--control", str(control)],
                  allow_cpu=True, spec_path=spec)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _unchanged(cfg, tc, blocked=None, *, state=None, **kw):
    if state is None:
        state = monitor.fleet_monitor_init(cfg, np.shape(tc)[0])
    return state, None


def _half(cfg, tc, blocked=None, *, state=None, **kw):
    q = np.shape(tc)[0]
    h = q // 2
    if state is None:
        state = monitor.fleet_monitor_init(cfg, q)
    kw["donate"] = False
    top, _ = REAL(cfg, np.asarray(tc)[:h], np.asarray(blocked)[:h],
                  state=jax.tree.map(lambda a: a[:h], state), **kw)
    return jax.tree.map(lambda a, b: jnp.concatenate([a, b[h:]]),
                        top, state), None


def _altered(cfg, tc, blocked=None, **kw):
    st, out = REAL(cfg, tc, blocked, **kw)
    return st._replace(last_qbar=st.last_qbar * 1.001,
                       mean=st.mean * 1.001), out


TILE = 8       # rows of the state: a twelfth of the test's 96 ends


def _tile_altered(first):
    """The converged estimates of one tile of rows written 10% high
    where they are produced: ``first`` is the tile's first row, or,
    negative, counted from the last real row."""
    def fault(cfg, tc, blocked=None, *, state=None, **kw):
        lo = first % np.shape(tc)[0]
        rows = slice(lo, lo + TILE)
        before = 0 if state is None else np.asarray(state.epoch[rows])
        st, out = REAL(cfg, tc, blocked, state=state, **kw)
        fresh = st.epoch[rows] > before
        bumped = jnp.where(fresh, st.last_qbar[rows] * 1.1,
                           st.last_qbar[rows])
        return st._replace(last_qbar=st.last_qbar.at[rows].set(bumped)), out
    return fault


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_control_is_not(small_spec, cell, capsys):
    line = _run(small_spec, cell, capsys, control=1)
    assert line["correct"] is True
    assert list(line)[-1] == "checks"
    limits = {k: v["limit"] for k, v in line["checks"].items()}
    assert set(line["control"]) == {"bf16", "bf16_eq4", "bf16_estimates"}
    for numbers in line["control"].values():
        low = dict(numbers, count_mismatch=0, window_mismatch=0)
        ok, _ = check.verdict(low, limits)
        assert not ok


@pytest.mark.parametrize("fault", [_unchanged, _half, _altered],
                         ids=["state_unchanged", "half_left_out",
                              "answer_altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_caught(small_spec, cell, fault, capsys, monkeypatch):
    monkeypatch.setattr(fleet, "run_monitor_fleet", fault)
    monkeypatch.setattr(monitor, "run_monitor_fleet", fault)
    line = _run(small_spec, cell, capsys)
    assert line["correct"] is False


@pytest.mark.parametrize("first", [40, -TILE], ids=["inner", "last_rows"])
@pytest.mark.parametrize("cell", CELLS)
def test_one_tile_altered_is_caught_by_the_largest_gap(
        small_spec, cell, first, capsys, monkeypatch):
    fault = _tile_altered(first)
    monkeypatch.setattr(fleet, "run_monitor_fleet", fault)
    monkeypatch.setattr(monitor, "run_monitor_fleet", fault)
    line = _run(small_spec, cell, capsys)
    over = [k for k, c in line["checks"].items() if c["value"] > c["limit"]
            or c["value"] != c["value"]]
    assert line["correct"] is False and over == ["rate_gap_max"]
