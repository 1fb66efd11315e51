# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV rows plus a per-benchmark verdict vs the paper's claim.  Run-level
# results (rows/verdict/status/seconds per benchmark) are merged into the
# canonical per-suite report ``BENCH_<suite>.json`` at the repo root under
# the ``"run"`` key — the same merge-on-update file the suite's own
# sections land in, so one file per suite tracks both the measured
# sections and the latest run's verdicts.  ``--json PATH`` additionally
# writes the whole run as one machine-readable report to an explicit
# path (scratch use; the canonical files are the source of truth).
#
# ``--seed N`` exports ``REPRO_BENCH_SEED`` so every suite's seeded
# draws — workload sample paths, chaos fault schedules — are
# reproducible end-to-end: same seed, same schedule, same verdict noise
# floor.
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _merge_canonical(suite: str, run_entry: dict) -> pathlib.Path:
    """Merge this run's entries into ``BENCH_<suite>.json`` (the one
    canonical artifact per suite): suite sections written by the
    benchmarks themselves are preserved, the ``"run"`` key is replaced."""
    path = ROOT / f"BENCH_{suite}.json"
    try:
        report = json.loads(path.read_text())
    except (FileNotFoundError, json.JSONDecodeError):
        report = {}
    report["run"] = run_entry
    path.write_text(json.dumps(report, indent=2, sort_keys=True))
    return path


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the whole run as JSON to PATH "
                         "(canonical BENCH_<suite>.json files are always "
                         "updated regardless)")
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark names")
    ap.add_argument("--suite", default=None,
                    choices=["paper", "apps", "kernels", "roofline",
                             "pipeline", "collector", "control"],
                    help="run only one suite (default: all)")
    ap.add_argument("--seed", type=int, default=None, metavar="N",
                    help="base seed exported as REPRO_BENCH_SEED to every "
                         "suite (workload sample paths, fault schedules)")
    args = ap.parse_args(argv)

    if args.seed is not None:
        os.environ["REPRO_BENCH_SEED"] = str(args.seed)

    from repro.core.backend import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (apps, collector_bench, control_bench,
                            kernel_bench, paper_figs, pipeline_bench,
                            roofline_table)

    suites = [("paper", paper_figs.ALL), ("apps", apps.ALL),
              ("kernels", kernel_bench.ALL),
              ("roofline", roofline_table.ALL),
              ("pipeline", pipeline_bench.ALL),
              ("collector", collector_bench.ALL),
              ("control", control_bench.ALL)]
    if args.suite:
        suites = [s for s in suites if s[0] == args.suite]
    print("name,us_per_call,derived")
    report: dict = {}
    n_fail = 0
    t0 = time.time()
    for suite, fns in suites:
        t_suite = time.time()
        entry = report.setdefault(suite, {})
        ran_any = False
        for fn in fns:
            if args.only and args.only not in fn.__name__:
                continue
            ran_any = True
            t_fn = time.time()
            try:
                rows, verdict = fn()
                for r in rows:
                    print(r, flush=True)
                print(f"# VERDICT {suite}/{fn.__name__}: {verdict}",
                      flush=True)
                entry[fn.__name__] = {
                    "status": "ok", "rows": list(rows),
                    "verdict": verdict,
                    "seconds": round(time.time() - t_fn, 2)}
            except Exception as e:  # noqa: BLE001
                n_fail += 1
                print(f"# FAILED {suite}/{fn.__name__}:", flush=True)
                traceback.print_exc()
                entry[fn.__name__] = {
                    "status": "error",
                    "error": f"{type(e).__name__}: {e}",
                    "seconds": round(time.time() - t_fn, 2)}
        if ran_any:
            entry["_meta"] = {
                "seconds": round(time.time() - t_suite, 1),
                "seed": args.seed,
                "quick": bool(os.environ.get("REPRO_BENCH_QUICK")),
                "only": args.only}
            path = _merge_canonical(suite, entry)
            print(f"# canonical report -> {path}", flush=True)
    report["_meta"] = {"total_seconds": round(time.time() - t0, 1),
                       "failures": n_fail}
    print(f"# done in {time.time() - t0:.0f}s, failures={n_fail}",
          flush=True)
    if args.json:
        path = pathlib.Path(args.json)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(report, indent=2))
        print(f"# json report -> {path}", flush=True)
    if n_fail:
        sys.exit(1)


if __name__ == '__main__':
    main()
