"""Schema-versioned benchmark reports and baseline regression gating.

Report files are named `BENCH_<tag>.json` and live at the repo root (the
benchmark trajectory of the project); `benchmarks/baseline.json` is the
committed throughput baseline CI compares against.

Schema (version 2):

    {
      "schema_version": 2,
      "tag": "...", "suite": "smoke", "created_unix": 1e9,
      "host": {"platform": ..., "python": ..., "jax": ..., "backend": ...,
               "device_kind": ..., "device_count": ...},
      "statuses": {"ok": 12, "timeout": 1, ...},
      "records": [ {<runner.run_entry record>}, ... ],
      "robustness": {<benchmarks.robustness section>}   # optional
    }

Every record carries `status`: "ok" | "timeout" | "error" | "skipped"
(see `benchmarks.runner`); non-ok records keep identity fields plus an
`error` message and are EXCLUDED from baselines, gating, and the nightly
rollup (`ok_records`) — a partial run stays schema-valid and commits
whatever it measured. The baseline holds the same header plus per-id
throughput numbers only.
Regression policy: CI fails when the *geometric mean* over per-record
`chain_steps_per_s` ratios (new/baseline) drops below `1 - threshold`
(default 30%). Per-record ratios are reported for diagnosis but do not gate
individually — single records are too noisy on shared CI runners.
"""
from __future__ import annotations

import json
import os
import platform
import sys
import time

import jax

SCHEMA_VERSION = 2
REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
BASELINE_PATH = os.path.join(os.path.dirname(__file__), "baseline.json")
DEFAULT_THRESHOLD = 0.30


def git_commit() -> "str | None":
    """Commit SHA the report was produced from: GITHUB_SHA in CI, else
    `git rev-parse HEAD`, else None (e.g. a source tarball)."""
    sha = os.environ.get("GITHUB_SHA")
    if sha:
        return sha
    try:
        import subprocess

        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=REPO_ROOT,
            capture_output=True, text=True, timeout=10,
        )
    except (OSError, subprocess.SubprocessError):
        return None
    return out.stdout.strip() or None if out.returncode == 0 else None


def use_compile_cache(root: str = REPO_ROOT) -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX has already read it and it
    is left alone. Otherwise the cache goes to `<root>/.jax_cache`: a fixed
    path, because the path is part of the cache key, so a directory that
    moved would never hit. Entry points call this (`benchmarks.run`,
    `chip_smoke.py`); importing the package never does.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def host_info() -> dict:
    """Host identity header for a report (platform, device, jax, CI flag,
    commit)."""
    devices = jax.devices()
    return {
        "platform": platform.platform(),
        "machine": platform.machine(),
        "python": sys.version.split()[0],
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        # True when produced by a GitHub Actions runner: only then are the
        # absolute throughput numbers comparable to later CI runs, and only
        # then does the regression gate fail hard (see compare_to_baseline).
        "ci": bool(os.environ.get("GITHUB_ACTIONS")),
        "commit": git_commit(),
    }


def ok_records(report_or_records) -> list[dict]:
    """The measured records only (`status` "ok", or absent — pre-status
    reports never recorded failures, so every record in one is a
    measurement). Baselines, gating, and the nightly rollup all consume
    this view; timeout/error/skipped records stay in the full report."""
    records = (
        report_or_records.get("records", [])
        if isinstance(report_or_records, dict) else report_or_records
    )
    return [r for r in records if r.get("status", "ok") == "ok"]


def status_counts(records: list[dict]) -> dict:
    """{"ok": n, "timeout": n, ...} — only statuses that occur."""
    counts: dict = {}
    for r in records:
        status = r.get("status", "ok")
        counts[status] = counts.get(status, 0) + 1
    return counts


def _atomic_write_json(path: str, obj) -> None:
    """Write strict JSON via a same-directory tmp file + `os.replace`.

    A reader (or a later append) can never observe a truncated file: the
    replace is atomic on POSIX and Windows, and an interrupted write leaves
    the previous contents untouched (the orphaned tmp file is re-created,
    then replaced, by the next successful write).
    """
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            # allow_nan=False: reports must be strict RFC-8259 JSON (no
            # Infinity/NaN tokens) so jq/JS consumers of CI artifacts parse.
            json.dump(obj, f, indent=1, sort_keys=True, allow_nan=False)
            f.write("\n")
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def make_report(
    tag: str, suite: str, records: list[dict], scaling: "dict | None" = None,
    robustness: "dict | None" = None,
) -> dict:
    """Assemble a schema-v2 report dict (see the module docstring).

    `scaling` is the optional async-vs-sync scaling-law section produced by
    `benchmarks.scaling.scaling_section` — carried verbatim under the
    report's "scaling" key (absent when the run did not sweep it); the
    section versions itself via its own "schema_version" field.
    `robustness` is the analogous fault-severity section produced by
    `benchmarks.robustness.robustness_section` (see docs/robustness.md).
    """
    report = {
        "schema_version": SCHEMA_VERSION,
        "tag": tag,
        "suite": suite,
        "created_unix": time.time(),
        "created": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "host": host_info(),
        "statuses": status_counts(records),
        "records": records,
    }
    if scaling is not None:
        report["scaling"] = scaling
    if robustness is not None:
        report["robustness"] = robustness
    return report


def report_path(tag: str, out_dir: str = REPO_ROOT) -> str:
    """Path of BENCH_<tag>.json under out_dir (tag 'nightly' reserved)."""
    path = os.path.join(out_dir, f"BENCH_{tag}.json")
    if os.path.abspath(path) == os.path.abspath(NIGHTLY_PATH):
        raise ValueError(
            "tag 'nightly' is reserved: BENCH_nightly.json at the repo root "
            "is the committed trajectory that --append-nightly extends; "
            "writing a full report there would destroy it (pick another "
            "tag, e.g. 'nightly-full')"
        )
    return path


def write_report(report: dict, out_dir: str = REPO_ROOT) -> str:
    """Write a report as strict JSON (atomically); returns the path."""
    path = report_path(report["tag"], out_dir)
    _atomic_write_json(path, report)
    return path


def load(path: str) -> dict:
    """Load a report, enforcing the supported schema version."""
    with open(path) as f:
        report = json.load(f)
    version = report.get("schema_version")
    if version != SCHEMA_VERSION:
        raise ValueError(
            f"{path}: schema_version {version!r} != supported {SCHEMA_VERSION} "
            "(refresh with `python -m benchmarks.run --smoke --update-baseline`)"
        )
    return report


def to_baseline(report: dict) -> dict:
    """Slim a full report down to the committed throughput baseline.

    Only measured records contribute — a timeout/error entry has no
    throughput, and freezing its absence into the baseline would just list
    it as "missing" forever."""
    return {
        "schema_version": SCHEMA_VERSION,
        "tag": report["tag"],
        "suite": report["suite"],
        "created": report.get("created"),
        "host": report["host"],
        "throughput": {
            r["id"]: {
                "chain_steps_per_s": r["chain_steps_per_s"],
                "steps_per_s": r["steps_per_s"],
                "wall_s": r["wall_s"],
            }
            for r in ok_records(report)
        },
    }


NIGHTLY_PATH = os.path.join(REPO_ROOT, "BENCH_nightly.json")


def _geomean(values) -> float:
    """Floored geometric mean — the one statistic both the regression gate
    and the nightly trajectory report, so they can never diverge."""
    import numpy as np

    return float(np.exp(np.mean(np.log(np.maximum(list(values), 1e-12)))))


def nightly_record(report: dict) -> dict:
    """Trim a full report to one nightly-trajectory point: geomean
    throughput and TTS hit rate per kernel, plus enough host identity to
    attribute runner variance. Full per-entry records stay in the run's
    artifact; the committed trajectory only needs the trend."""
    import numpy as np

    per_kernel: dict = {}
    for rec in ok_records(report):
        per_kernel.setdefault(rec["kernel"], []).append(rec)
    kernels = {}
    for kernel, recs in sorted(per_kernel.items()):
        kernels[kernel] = {
            "entries": len(recs),
            "geomean_chain_steps_per_s": _geomean(
                r["chain_steps_per_s"] for r in recs
            ),
            "hit_rate": float(np.mean([r["hit_rate"] for r in recs])),
        }
    record = {
        "tag": report["tag"],
        "suite": report["suite"],
        "created": report.get("created"),
        "host": {
            k: report["host"].get(k)
            for k in ("platform", "python", "jax", "ci", "commit")
        },
        "n_records": len(report["records"]),
        "statuses": status_counts(report["records"]),
        "kernels": kernels,
    }
    if "scaling" in report:
        record["scaling"] = scaling_rollup(report["scaling"])
    return record


def scaling_rollup(section: dict) -> dict:
    """Trim a full scaling section to its trajectory essentials: per
    problem, each kernel's fitted exponent B and the async-vs-sync
    exponent-gap p-values. CIs, per-size medians, and mixing summaries
    stay in the full report artifact."""
    out = {}
    for problem, rec in sorted(section.get("problems", {}).items()):
        out[problem] = {
            "B": {
                kernel: (None if kr["fit"] is None else kr["fit"]["B"])
                for kernel, kr in sorted(rec["kernels"].items())
            },
            "pvalue_vs_sync": {
                kernel: g["pvalue"]
                for kernel, g in sorted(rec["gap_vs_sync"].items())
            },
        }
    return out


def append_nightly(report: dict, path: str = NIGHTLY_PATH) -> tuple[dict, bool]:
    """Append `report`'s trimmed record to the committed nightly trajectory.

    The trajectory file holds {"schema_version", "records": [...]} ordered
    oldest-first — successive nightly runs make runner variance visible
    instead of leaving reviewers to guess it from two baselines.

    Returns (trajectory, appended). A record whose commit SHA already
    appears in the trajectory is NOT appended (appended=False, file
    untouched): nightly re-runs of the same commit (workflow retries,
    manual dispatches) would otherwise pile up duplicate points and fake
    runner variance. Records with no SHA (non-git checkouts) always append.
    """
    if os.path.exists(path):
        with open(path) as f:
            trajectory = json.load(f)
        version = trajectory.get("schema_version")
        if version != SCHEMA_VERSION:
            raise ValueError(
                f"{path}: schema_version {version!r} != supported {SCHEMA_VERSION}"
            )
        # Full suite reports share schema_version and a "records" key;
        # appending onto one would silently destroy the trajectory. Trimmed
        # trajectory records are distinguishable by their "kernels" rollup.
        if any("kernels" not in r for r in trajectory["records"]):
            raise ValueError(
                f"{path} holds full per-entry records, not a nightly "
                "trajectory — refusing to append (was a full report written "
                "over the trajectory file?)"
            )
    else:
        trajectory = {"schema_version": SCHEMA_VERSION, "records": []}
    record = nightly_record(report)
    sha = record["host"].get("commit")
    if sha is not None and any(
        r.get("host", {}).get("commit") == sha for r in trajectory["records"]
    ):
        return trajectory, False
    trajectory["records"].append(record)
    # Atomic replace: a scheduled run killed mid-write must never leave a
    # truncated trajectory behind — the previous complete file survives.
    _atomic_write_json(path, trajectory)
    return trajectory, True


def compare_to_baseline(
    report: dict, baseline: dict, threshold: float = DEFAULT_THRESHOLD
) -> tuple[bool, dict]:
    """Gate `report` against `baseline` throughput.

    Returns (ok, summary). summary["ratios"] maps record id ->
    new/baseline chain_steps_per_s; summary["geomean_ratio"] is the gate
    quantity; ids present on only one side are listed, not gated. A report
    with NO overlapping ids fails outright — an id-scheme change must not
    turn the gate vacuous. When the baseline was not produced in CI
    (host.ci false — e.g. a dev machine), absolute throughput is not
    comparable to CI runners: a regression is reported as advisory
    (summary["advisory"] = True) and ok stays True.
    """
    base = baseline["throughput"]
    measured = ok_records(report)
    ratios, missing, new_ids = {}, [], []
    for rec in measured:
        rid = rec["id"]
        if rid in base:
            ratios[rid] = rec["chain_steps_per_s"] / max(base[rid]["chain_steps_per_s"], 1e-12)
        else:
            new_ids.append(rid)
    # A baselined entry that timed out / errored this run shows up as
    # missing — visible in the summary rather than silently ungated.
    report_ids = {r["id"] for r in measured}
    missing = sorted(set(base) - report_ids)

    if ratios:
        geomean = _geomean(ratios.values())
        passed = geomean >= 1.0 - threshold
        error = None
    else:
        geomean = None
        passed = False
        error = ("no overlapping record ids between report and baseline — "
                 "the gate would be vacuous; refresh the baseline")
    advisory = (not passed) and error is None and not baseline["host"].get("ci", False)
    summary = {
        "geomean_ratio": geomean,
        "threshold": threshold,
        "ok": passed or advisory,
        "passed": passed,
        "advisory": advisory,
        "error": error,
        "ratios": ratios,
        "new_ids": new_ids,
        "missing_ids": missing,
        "worst": min(ratios, key=ratios.get) if ratios else None,
    }
    return summary["ok"], summary


def format_comparison(summary: dict) -> str:
    """Human-readable comparison summary for the gate's stdout."""
    lines = []
    for rid, ratio in sorted(summary["ratios"].items(), key=lambda kv: kv[1]):
        flag = " <-- slow" if ratio < 1.0 - summary["threshold"] else ""
        lines.append(f"  {ratio:6.2f}x  {rid}{flag}")
    for rid in summary["new_ids"]:
        lines.append(f"     new  {rid}")
    for rid in summary["missing_ids"]:
        lines.append(f" missing  {rid}")
    if summary["error"]:
        lines.append(f"ERROR: {summary['error']}")
    else:
        if summary["passed"]:
            verdict = "OK"
        elif summary["advisory"]:
            verdict = ("REGRESSION vs a non-CI baseline — ADVISORY ONLY "
                       "(absolute throughput not comparable across machines; "
                       "refresh the baseline from a CI artifact to arm the gate)")
        else:
            verdict = "REGRESSION"
        lines.append(
            f"throughput geomean ratio {summary['geomean_ratio']:.3f} "
            f"(gate: >= {1.0 - summary['threshold']:.2f}) -> {verdict}"
        )
    return "\n".join(lines)
