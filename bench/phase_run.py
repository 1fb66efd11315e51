#!/usr/bin/env python3
"""One traced run of a cell, its device time split by the estimator's
phase and its host time by program span.

    python3 bench/phase_run.py --workload <config>.<mix> --seed N \
        --seconds S [--spec PATH]

Drives the cell as ``bench/run.py --trace 1`` does (the configuration's
runner and its traced window) and reduces the one trace with both
``harness.tracefile`` and ``harness.phases`` before it is removed.
Prints one JSON line: ``phases`` (``harness.phases.metrics``:
``compact_device_ms``, ``window_device_ms``, ``detect_device_ms``,
``carry_device_ms``, ``unscoped_device_ms``, ``compute_device_ms``,
``stage_ms``), ``estimator_device_ms`` and ``device_idle_share`` as
``run.py`` reads them, ``fold_rate`` over the whole window (traced in
part, as the runner traces it), the reduction (``scope_s``, ``inferred_s``,
``span_s``, ``idle_gaps``) and ``correct``.  ``--spec`` names another
``BENCHMARK.json``, for a cell the accepted one does not hold.  Exits
non-zero when JAX finds no accelerator.
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


def main(argv=None, *, allow_cpu: bool = False, t0: float = T0) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--spec", default=str(ROOT / "BENCHMARK.json"))
    args = p.parse_args(argv)
    from harness import check, phases, spec, tracefile

    cell = spec.load_cell(args.workload, args.seed, args.seconds, True,
                          Path(args.spec))
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(BENCH / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform == "cpu" and not allow_cpu:
        print("no accelerator: JAX found only the CPU", file=sys.stderr)
        return 2

    runner = spec.load_module(BENCH / "runners" /
                              f"{cell.config['runner']}.py",
                              f"runner_{cell.config['runner']}")
    rec = runner.run(cell, t0)
    tracer = rec["tracer"]
    prefix = cell.config["estimator_module"]
    try:
        base = tracefile.reduce_dir(tracer.dir, prefix)
        r = phases.reduce_dir(tracer.dir, prefix)
    finally:
        tracer.close()
    read = {name: spec.load_module(spec.reader_path(name),
                                   f"metric_{name}").read({"trace": base})
            for name in ("estimator_device_ms", "device_idle_share")}
    read["fold_rate"] = spec.load_module(spec.reader_path("fold_rate"),
                                         "metric_fold_rate").read(rec)
    correct, checks = check.verdict(rec["numbers"], cell.config["checks"])
    print(json.dumps({
        "correct": correct, "workload": cell.name, "seed": cell.seed,
        "device": jax.devices()[0].device_kind,
        "phases": phases.metrics(r), **read,
        "scope_s": r["scope_s"], "unscoped_s": r["unscoped_s"],
        "inferred_s": r["inferred_s"], "executions": r["executions"],
        "span_s": r["span_s"], "idle_gaps": r["idle_gaps"],
        "window_s": base["window_s"], "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
