"""Benchmark CLI — suites, regression gating, and the paper figures.

    PYTHONPATH=src python -m benchmarks.run --smoke
        Run the CI smoke suite (tiny sizes/steps) and write BENCH_<tag>.json
        at the repo root.

    PYTHONPATH=src python -m benchmarks.run --smoke --check-baseline
        Also compare throughput against benchmarks/baseline.json; exit 1 on
        a geomean regression beyond --threshold (CI's bench-smoke job).

    PYTHONPATH=src python -m benchmarks.run --smoke --update-baseline
        Refresh the committed baseline from this run (do this on purposeful
        perf changes, on the same class of machine as the old baseline).
        Combined with --check-baseline, the check runs against the OLD
        baseline before it is overwritten.

    PYTHONPATH=src python -m benchmarks.run --baseline-from BENCH_ci.json
        Adopt an existing report (e.g. a downloaded CI artifact) as the
        baseline without running anything. A report produced in CI carries
        host.ci=true, which arms the hard regression gate.

    PYTHONPATH=src python -m benchmarks.run --suite full --tag nightly-full --append-nightly
        The nightly suite; --append-nightly extends the committed
        BENCH_nightly.json trajectory with a trimmed per-kernel record.
        (The tag "nightly" itself is reserved for the trajectory file.)

    PYTHONPATH=src python -m benchmarks.run --smoke --scaling smoke
        Also run the async-vs-sync TTS scaling-law sweep (benchmarks/
        scaling.py) and embed its section in the report (and, with
        --append-nightly, a trimmed exponent/p-value rollup in the
        trajectory record). Grids: "smoke" (PR-sized) or "full" (nightly).

    PYTHONPATH=src python -m benchmarks.run --smoke --robustness smoke
        Also run the fault-severity robustness sweep (benchmarks/
        robustness.py: TTS/hit-rate vs quantization bits and stuck-spin
        fraction, plus ideal-limit distribution sanity checks) and embed
        its section in the report.

    PYTHONPATH=src python -m benchmarks.run --suite full --isolate --timeout 1800
        Crash-safe mode: each entry runs in its own worker subprocess with
        a per-entry wall-clock budget; hangs/crashes become per-record
        status "timeout"/"error" and the report still commits everything
        measured (see benchmarks/runner.py).

    PYTHONPATH=src python -m benchmarks.run --figures [--only fig3a] [--fast]
        The legacy per-paper-figure benchmarks (CSV to stdout).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from benchmarks import report as report_mod
from benchmarks import robustness as robustness_mod
from benchmarks import runner, scaling, suites
from benchmarks.figures import run_figures


def main(argv: list[str] | None = None) -> int:
    """CLI entry point; returns the process exit code."""
    ap = argparse.ArgumentParser(prog="benchmarks.run", description=__doc__)
    ap.add_argument("--suite", default=None, choices=sorted(suites.SUITES),
                    help="suite to run (default: smoke)")
    ap.add_argument("--smoke", action="store_true", help="alias for --suite smoke")
    ap.add_argument("--tag", default=None,
                    help="report tag -> BENCH_<tag>.json (default: <suite>-<utc time>)")
    ap.add_argument("--out", default=report_mod.REPO_ROOT,
                    help="directory for BENCH_<tag>.json (default: repo root)")
    ap.add_argument("--check-baseline", action="store_true",
                    help="compare against --baseline; exit 1 on regression")
    ap.add_argument("--update-baseline", action="store_true",
                    help="write this run's throughput as the new baseline")
    ap.add_argument("--baseline", default=report_mod.BASELINE_PATH,
                    help="baseline path (default: benchmarks/baseline.json)")
    ap.add_argument("--baseline-from", default=None, metavar="REPORT",
                    help="adopt an existing BENCH_*.json as the baseline and exit "
                         "(no suite run); use on a downloaded CI artifact to arm "
                         "the hard gate")
    ap.add_argument("--threshold", type=float, default=report_mod.DEFAULT_THRESHOLD,
                    help="max allowed geomean throughput drop (default 0.30)")
    ap.add_argument("--append-nightly", nargs="?", const=report_mod.NIGHTLY_PATH,
                    default=None, metavar="PATH",
                    help="append this run's trimmed record (per-kernel geomean "
                         "throughput + hit rates) to the committed nightly "
                         "trajectory (default: BENCH_nightly.json)")
    ap.add_argument("--scaling", default=None, choices=sorted(scaling.SCALING_SPECS),
                    help="also run the async-vs-sync TTS scaling sweep on this "
                         "grid and embed its section in the report")
    ap.add_argument("--robustness", default=None,
                    choices=sorted(robustness_mod.SWEEP_SPECS),
                    help="also run the fault-severity robustness sweep on this "
                         "grid and embed its section in the report")
    ap.add_argument("--isolate", action="store_true",
                    help="run each entry in a worker subprocess (crashes "
                         "become per-record status 'error')")
    ap.add_argument("--timeout", type=float, default=None, metavar="SECONDS",
                    help="per-entry wall-clock budget (requires --isolate; "
                         "hangs become status 'timeout')")
    ap.add_argument("--retries", type=int, default=runner.DEFAULT_RETRIES,
                    help="retries (with backoff) for transient entry errors "
                         f"(default {runner.DEFAULT_RETRIES}; timeouts never retry)")
    ap.add_argument("--figures", action="store_true",
                    help="run the paper-figure benchmarks instead of a suite")
    ap.add_argument("--only", default=None, help="(--figures) substring filter")
    ap.add_argument("--fast", action="store_true", help="(--figures) reduced sizes")
    args = ap.parse_args(argv)

    if args.figures:
        run_figures(only=args.only, fast=args.fast)
        return 0
    if args.only or args.fast:
        ap.error("--only/--fast apply to the figure benchmarks; add --figures")
    if args.timeout is not None and not args.isolate:
        ap.error("--timeout requires --isolate (an in-process entry cannot "
                 "be interrupted)")

    if args.baseline_from:
        rep = report_mod.load(args.baseline_from)
        with open(args.baseline, "w") as f:
            json.dump(report_mod.to_baseline(rep), f, indent=1, sort_keys=True)
            f.write("\n")
        armed = rep["host"].get("ci", False)
        print(f"baseline {args.baseline} <- {args.baseline_from} "
              f"(host.ci={armed}: hard gate {'ARMED' if armed else 'advisory'})")
        return 0

    if args.smoke and args.suite not in (None, "smoke"):
        ap.error(f"--smoke conflicts with --suite {args.suite}")
    suite_name = "smoke" if args.smoke else (args.suite or "smoke")
    entries = suites.get_suite(suite_name)
    tag = args.tag or f"{suite_name}-{time.strftime('%Y%m%d-%H%M%S', time.gmtime())}"

    # Load the baseline BEFORE --update-baseline can overwrite it: checking
    # a run against a baseline written from itself would always pass.
    old_baseline = report_mod.load(args.baseline) if args.check_baseline else None

    print(f"suite={suite_name} entries={len(entries)} tag={tag}", flush=True)
    t0 = time.perf_counter()
    records = runner.run_suite(
        entries, log=lambda m: print(m, flush=True),
        timeout_s=args.timeout, isolate=args.isolate, retries=args.retries,
    )
    print(f"suite wall time: {time.perf_counter() - t0:.1f}s")
    statuses = report_mod.status_counts(records)
    failed = set(statuses) - {"ok"}
    if failed:
        print(f"entry statuses: {statuses}")

    scaling_section = None
    if args.scaling:
        t0 = time.perf_counter()
        scaling_section = scaling.scaling_section(
            scaling.get_scaling_specs(args.scaling),
            log=lambda m: print(m, flush=True),
        )
        print(f"scaling wall time: {time.perf_counter() - t0:.1f}s")

    robustness_section = None
    if args.robustness:
        t0 = time.perf_counter()
        robustness_section = robustness_mod.robustness_section(
            args.robustness, log=lambda m: print(m, flush=True)
        )
        print(f"robustness wall time: {time.perf_counter() - t0:.1f}s")

    rep = report_mod.make_report(
        tag, suite_name, records, scaling=scaling_section,
        robustness=robustness_section,
    )
    path = report_mod.write_report(rep, args.out)
    print(f"wrote {path}")

    if args.append_nightly:
        trajectory, appended = report_mod.append_nightly(rep, args.append_nightly)
        if appended:
            print(f"appended nightly record #{len(trajectory['records'])} "
                  f"to {args.append_nightly}")
        else:
            print(f"skipped nightly append: commit "
                  f"{rep['host'].get('commit')} already recorded in "
                  f"{args.append_nightly}")

    if args.update_baseline:
        with open(args.baseline, "w") as f:
            json.dump(report_mod.to_baseline(rep), f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"updated baseline {args.baseline}")

    if args.check_baseline:
        ok, summary = report_mod.compare_to_baseline(rep, old_baseline, args.threshold)
        print(report_mod.format_comparison(summary))
        if not ok:
            return 1
    if failed:
        # The report above keeps every record; the exit code must not hide
        # an entry that timed out or errored.
        print(f"FAILED: {len(records) - statuses.get('ok', 0)} of {len(records)} "
              f"entries did not finish ok")
        return 1
    return 0


if __name__ == "__main__":
    # Only the command line places the compile cache: tests call main()
    # and must not start writing one.
    report_mod.use_compile_cache()
    sys.exit(main())
