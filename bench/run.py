#!/usr/bin/env python3
"""Benchmark entry point: one run of one cell.

    python3 bench/run.py --workload <config>.<mix> --seed N \
        --seconds S --trace 0|1

Sets up the cell (traffic from the seed, the system as a deployment
builds it, every program compiled and warmed up), measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON line: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device`` and, traced, a
``breakdown``; the numbers compared come last, under ``checks``, and
also as the last lines of standard error.  Exits non-zero, printing no
result, when JAX finds no accelerator or fewer chips than the cell asks.

``--control 1`` also computes the lower-precision control's numbers
(the reference in bfloat16 in the program's place); benchmark runs
leave it off.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(BENCH), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _args(argv):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None, *, allow_cpu: bool = False, t0: float = T0,
         spec_path: Path = ROOT / "BENCHMARK.json") -> int:
    args = _args(argv)
    from harness import check, spec, tracefile

    cell = spec.load_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), spec_path)
    cell.control = bool(args.control)

    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(BENCH / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform == "cpu" and not allow_cpu:
        print("no accelerator: JAX found only the CPU", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"the cell needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    runner = spec.load_module(BENCH / "runners" /
                              f"{cell.config['runner']}.py",
                              f"runner_{cell.config['runner']}")
    rec = runner.run(cell, t0)
    kind = devices[0].device_kind
    rec["device_kind"] = kind
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": rec["memory_peak_bytes"]}
    out = {}
    tracer = rec["tracer"]
    try:
        if cell.trace:
            rec["trace"] = tracefile.reduce_dir(
                tracer.dir, cell.config["estimator_module"])
            device["busy_s"] = rec["trace"]["busy_s"]
            device["window_s"] = rec["trace"]["window_s"]
            out["breakdown"] = {
                "device_ops": rec["trace"]["device_ops"],
                "idle_gaps": rec["trace"]["idle_gaps"]}
    finally:
        tracer.close()

    metrics = {}
    for m in (cell.per_layer if cell.trace else cell.end_to_end):
        reader = spec.load_module(spec.reader_path(m["name"]),
                                  f"metric_{m['name']}")
        value = reader.read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    correct, checks = check.verdict(rec["numbers"], cell.config["checks"])
    line = {"correct": correct, "attempted": rec["attempted"],
            "failed": rec["failed"], "metrics": metrics, "device": device}
    line.update(out)
    line["run"] = {"seed": cell.seed, "periods": rec["periods"],
                   "window_s": rec["window_s"], "gc_s": rec["gc_s"],
                   **rec["settings"]}
    if rec["control"] is not None:
        line["control"] = rec["control"]
        print(f"control {json.dumps(rec['control'])}", file=sys.stderr)
    line["checks"] = checks
    check.report(checks)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
