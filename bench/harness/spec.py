"""Resolve a cell of ``BENCHMARK.json`` to its files, by name.

A cell names a configuration and a traffic mix.  The configuration's
file (``configs/<name>.json``) names the runner that drives it
(``runners/<runner>.py``); the mix is ``traffic/<name>.json``; every
metric is read by ``metrics/<name>.py``, or, for a quantity split by
the end-to-end metric it moves (``gc_s.live``, ``gc_s.trace``), by the
reader of the name before the first dot.  Adding a cell, a mix or a
metric therefore adds files and edits none.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path

from harness import traffic

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

__all__ = ["Cell", "load_cell", "load_module", "reader_path", "BENCH",
           "ROOT"]


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: traffic.Mix
    seed: int
    seconds: float
    trace: bool
    end_to_end: list = field(default_factory=list)   # metric entries
    per_layer: list = field(default_factory=list)
    control: bool = False   # also compute the lower-precision control


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader_path(metric: str) -> Path:
    own = BENCH / "metrics" / f"{metric}.py"
    return own if own.exists() else (
        BENCH / "metrics" / f"{metric.split('.')[0]}.py")


def _for_cell(metrics: list, cell: str) -> list:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(name: str, seed: int, seconds: float, trace: bool,
              spec_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    spec = json.loads(Path(spec_path).read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    mix = traffic.load_mix(BENCH / "traffic" / f"{w['traffic']}.json")
    return Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                seed=int(seed), seconds=float(seconds), trace=bool(trace),
                end_to_end=_for_cell(spec["end_to_end"], name),
                per_layer=_for_cell(spec["per_layer"], name))
