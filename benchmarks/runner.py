"""Execute suite entries through the unified sampling driver.

One `run_entry` call produces a flat JSON-ready record: identity fields from
the `SuiteEntry`, the zoo reference energy, throughput (cold-call compile
estimate plus the median steady-state wall clock over `TIMING_REPEATS`
warm end-to-end `run()` calls), first-hit time-to-solution
against the reference target, and a downsampled best-so-far energy-gap
trajectory in model time.

`run_suite` degrades gracefully instead of dying wholesale: every entry
yields a record whose `status` is one of

    "ok"      — measured; all metric fields present.
    "timeout" — exceeded the per-entry wall-clock budget (subprocess
                isolation only — an in-process hang cannot be interrupted);
                recorded immediately, no retry (deterministic hangs are not
                transient, and retrying would double the wasted wall time).
    "error"   — raised/crashed; retried once with backoff first (shared CI
                runners do throw transient OOM/flake), then recorded with
                the error message.
    "skipped" — never attempted (the operator interrupted the suite);
                recorded so the report accounts for every entry.

Non-ok records keep the identity fields and carry `error` instead of
metrics; `benchmarks.report` filters on status for baselines/gating, and
`benchmarks.run` exits nonzero when any record is not "ok".

With isolate=True the parent never initialises a JAX backend before its
children run: it builds no problem and calls no JAX function until the
last child has exited (the report's host header is read after the suite).
On a TPU host the process that initialises the backend holds the chip, so
a child started after that would fail or hang.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
from typing import Optional

import jax
import numpy as np

from repro.core import problems, sampler_api
from benchmarks.suites import SuiteEntry, entry_to_dict

# Max points kept in each record's energy-gap trajectory.
TRAJECTORY_POINTS = 40

# Steady-state timing measurements per entry (median taken). Smoke entries
# finish in milliseconds, where single-shot wall clocks have shown multi-x
# run-to-run swings — far above the CI gate's 30% margin. Repeats reuse the
# warm jit cache, so they cost steady-state wall time only. Entries whose
# warm wall already exceeds REPEAT_MAX_WALL_S (full-suite scale) keep one
# sample: long walls self-average, and repeating them would multiply
# nightly compute for nothing.
TIMING_REPEATS = 3
REPEAT_MAX_WALL_S = 1.0


def _best_so_far_gap(times: np.ndarray, energies: np.ndarray, ref: float):
    """[[model_time, best_energy_so_far - ref], ...] across all chains.

    times/energies: (n_chains, n_samples). Observations are pooled in model
    time; the gap is the running best over everything observed so far.
    """
    if energies.size == 0:
        return []
    t = times.reshape(-1)
    e = energies.reshape(-1)
    order = np.argsort(t, kind="stable")
    t, e = t[order], e[order]
    best = np.minimum.accumulate(e)
    if len(t) > TRAJECTORY_POINTS:
        idx = np.linspace(0, len(t) - 1, TRAJECTORY_POINTS).round().astype(int)
        t, best = t[idx], best[idx]
    return [[float(a), float(b - ref)] for a, b in zip(t, best)]


def run_entry(entry: SuiteEntry, zoo: Optional[problems.ZooProblem] = None) -> dict:
    """Run one benchmark entry and return its record dict.

    `zoo` lets the caller reuse an instantiated problem across the entries
    that share it (generation includes reference-energy estimation).
    """
    if zoo is None:
        zoo = entry.make_problem()
    target = zoo.target_energy(entry.rel_gap)
    faults = entry.make_faults(zoo.problem)

    def timed():
        """One timed end-to-end run() call -> (result, wall seconds)."""
        t0 = time.perf_counter()
        res = jax.block_until_ready(
            sampler_api.run(
                zoo.problem,
                entry.make_kernel(),
                entry.key(),
                n_steps=entry.n_steps,
                n_chains=entry.n_chains,
                sample_every=entry.sample_every,
                schedule=entry.resolve_schedule(),
                first_hit=target,
                backend=entry.backend,
                unroll=entry.unroll,
                faults=faults,
            )
        )
        return res, max(time.perf_counter() - t0, 1e-9)

    # Median steady-state wall time over repeats (identical keys -> identical
    # results; only the clock varies). Every sample times the same thing —
    # one full end-to-end run() call — so the median is apples-to-apples;
    # compile_s is the cold call's excess over the warm median (the same
    # estimator RunTiming documents). NOTE compile_s is process-level:
    # entries sharing a jit signature warm each other's cache, so only the
    # first such entry in a suite reports the real compile cost.
    res, cold_s = timed()
    walls = [timed()[1]]
    if walls[0] < REPEAT_MAX_WALL_S:
        walls += [timed()[1] for _ in range(TIMING_REPEATS - 1)]
    wall_s = float(np.median(walls))
    timing = sampler_api.RunTiming(
        compile_s=max(0.0, cold_s - wall_s),
        wall_s=wall_s,
        steps_per_s=entry.n_steps / wall_s,
        chain_steps_per_s=entry.n_steps * entry.n_chains / wall_s,
    )

    # Normalize to a leading chain axis for uniform reduction.
    lead = lambda x: np.asarray(x)[None] if entry.n_chains == 1 else np.asarray(x)
    energies = lead(res.energies)
    times = lead(res.times)
    hit = lead(res.hit)
    t_hit = lead(res.t_hit)
    final_e = lead(zoo.problem.energy(res.s))

    best_energy = float(min(energies.min(), final_e.min())) if energies.size else float(final_e.min())
    hits = np.asarray(hit, bool)
    # None (JSON null), not inf: reports must stay strict RFC-8259 JSON.
    tts = float(np.median(t_hit[hits])) if hits.any() else None

    return {
        "id": entry.id,
        "status": "ok",
        "problem": entry.problem,
        "instance": zoo.instance,
        "size": entry.size,
        "seed": entry.seed,
        "n_spins": zoo.n,
        "kernel": entry.kernel,
        "kernel_args": dict(entry.kernel_args),
        "problem_args": dict(entry.problem_args),
        "faults": faults.describe() if faults is not None else None,
        "backend": entry.backend,
        "unroll": entry.unroll,
        "schedule": list(entry.schedule) if entry.schedule else None,
        "n_steps": entry.n_steps,
        "n_chains": entry.n_chains,
        "sample_every": entry.sample_every,
        "ref_energy": zoo.ref_energy,
        "ref_kind": zoo.ref_kind,
        "rel_gap": entry.rel_gap,
        "target_energy": target,
        # throughput
        "compile_s": timing.compile_s,
        "wall_s": timing.wall_s,
        "steps_per_s": timing.steps_per_s,
        "chain_steps_per_s": timing.chain_steps_per_s,
        # solution quality
        "best_energy": best_energy,
        "final_gap": best_energy - zoo.ref_energy,
        "hit_rate": float(hits.mean()),
        "tts_model_time": tts,
        "gap_trajectory": _best_so_far_gap(times, energies, zoo.ref_energy),
    }


class EntryTimeout(Exception):
    """An isolated entry exceeded its wall-clock budget (and was killed)."""


REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SRC_DIR = os.path.join(REPO_ROOT, "src")

# Retry-with-backoff policy for status "error" (see the module docstring:
# timeouts are never retried).
DEFAULT_RETRIES = 1
DEFAULT_BACKOFF_S = 2.0

# Tail of a failed worker's stderr kept in the record (enough for the
# traceback that matters without bloating the report).
STDERR_TAIL_CHARS = 2000


def error_record(entry: SuiteEntry, status: str, error: Optional[str]) -> dict:
    """A schema-valid record for an entry that produced no measurement.

    Identity fields only — metric fields are absent, `status` says why and
    `error` carries the message (None for "skipped"). Report consumers
    (baseline, gate, nightly rollup) filter on status.
    """
    return {
        "id": entry.id,
        "status": status,
        "error": error,
        "problem": entry.problem,
        "size": entry.size,
        "seed": entry.seed,
        "kernel": entry.kernel,
        "kernel_args": dict(entry.kernel_args),
        "problem_args": dict(entry.problem_args),
        "faults": dict(entry.faults) if entry.faults else None,
        "backend": entry.backend,
        "unroll": entry.unroll,
        "n_steps": entry.n_steps,
        "n_chains": entry.n_chains,
    }


def _run_entry_subprocess(entry: SuiteEntry, timeout_s: Optional[float]) -> dict:
    """Run one entry in a `benchmarks.entry_worker` child process.

    The parent must not have initialised a JAX backend at this point (see
    the module docstring): the child needs the device to itself. Raises EntryTimeout when the child exceeds `timeout_s` (it is killed),
    RuntimeError (with the stderr tail) when it exits nonzero or writes no
    record.
    """
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (SRC_DIR, env.get("PYTHONPATH")) if p
    )
    with tempfile.TemporaryDirectory(prefix="bench-entry-") as tmp:
        spec_path = os.path.join(tmp, "spec.json")
        record_path = os.path.join(tmp, "record.json")
        with open(spec_path, "w") as f:
            json.dump({"id": entry.id, "entry": entry_to_dict(entry)}, f)
        cmd = [sys.executable, "-m", "benchmarks.entry_worker", spec_path, record_path]
        try:
            proc = subprocess.run(
                cmd, cwd=REPO_ROOT, env=env, timeout=timeout_s,
                capture_output=True, text=True,
            )
        except subprocess.TimeoutExpired:
            raise EntryTimeout(
                f"{entry.id}: exceeded per-entry timeout of {timeout_s:.0f}s"
            ) from None
        if proc.returncode != 0 or not os.path.exists(record_path):
            tail = (proc.stderr or "")[-STDERR_TAIL_CHARS:].strip()
            raise RuntimeError(
                f"{entry.id}: worker exit code {proc.returncode}"
                + (f"\n{tail}" if tail else "")
            )
        with open(record_path) as f:
            return json.load(f)


def run_entry_safe(
    entry: SuiteEntry,
    zoo: Optional[problems.ZooProblem] = None,
    *,
    timeout_s: Optional[float] = None,
    isolate: bool = False,
    retries: int = DEFAULT_RETRIES,
    backoff_s: float = DEFAULT_BACKOFF_S,
    log=print,
) -> dict:
    """`run_entry` that always returns a record (status ok|timeout|error).

    Timeouts are recorded immediately; errors are retried `retries` times
    with linear backoff before an "error" record is written. `zoo` reuse
    only applies in-process (an isolated child regenerates its problem —
    that is the price of crash isolation).
    """
    last_error = None
    for attempt in range(1 + max(0, retries)):
        if attempt:
            log(f"  retry {attempt}/{retries} for {entry.id} "
                f"after {backoff_s * attempt:.0f}s: {last_error}")
            time.sleep(backoff_s * attempt)
        try:
            if isolate:
                rec = _run_entry_subprocess(entry, timeout_s)
            else:
                rec = run_entry(entry, zoo)
            rec["attempts"] = attempt + 1
            return rec
        except EntryTimeout as exc:
            rec = error_record(entry, "timeout", str(exc))
            rec["attempts"] = attempt + 1
            return rec
        except KeyboardInterrupt:
            raise
        except Exception as exc:  # noqa: BLE001 — the whole point is survival
            last_error = f"{type(exc).__name__}: {exc}"
    rec = error_record(entry, "error", last_error)
    rec["attempts"] = 1 + max(0, retries)
    return rec


def run_suite(
    entries: list[SuiteEntry],
    log=print,
    *,
    timeout_s: Optional[float] = None,
    isolate: bool = False,
    retries: int = DEFAULT_RETRIES,
    backoff_s: float = DEFAULT_BACKOFF_S,
) -> list[dict]:
    """Run a whole suite; every entry yields a record whatever happens.

    In-process (isolate=False, the default) entries reuse zoo instances
    across same-problem entries and exceptions become "error" records;
    isolate=True runs each entry in a worker subprocess so `timeout_s` can
    kill hangs ("timeout" records) and crashes cannot take the suite down.
    Ctrl-C marks the remaining entries "skipped" and returns the partial
    record list instead of discarding everything measured so far.
    """
    if timeout_s is not None and not isolate:
        raise ValueError(
            "timeout_s requires isolate=True — an in-process entry cannot "
            "be interrupted from the outside"
        )
    cache: dict[tuple, problems.ZooProblem] = {}
    records: list[dict] = []
    for i, entry in enumerate(entries):
        try:
            zoo = None
            if not isolate:
                pkey = (entry.problem, entry.size, entry.seed, entry.problem_args)
                try:
                    if pkey not in cache:
                        cache[pkey] = entry.make_problem()
                    zoo = cache[pkey]
                except Exception:  # noqa: BLE001 — run_entry retries/records it
                    zoo = None
            rec = run_entry_safe(
                entry, zoo, timeout_s=timeout_s, isolate=isolate,
                retries=retries, backoff_s=backoff_s, log=log,
            )
        except KeyboardInterrupt:
            log(f"interrupted — marking {len(entries) - i} remaining "
                "entries skipped")
            records.extend(error_record(e, "skipped", None) for e in entries[i:])
            break
        records.append(rec)
        if rec["status"] == "ok":
            log(
                f"[{i + 1}/{len(entries)}] {rec['id']}: "
                f"{rec['chain_steps_per_s']:.0f} chain-steps/s, "
                f"gap={rec['final_gap']:.3f}, hit_rate={rec['hit_rate']:.2f}"
            )
        else:
            log(f"[{i + 1}/{len(entries)}] {rec['id']}: "
                f"{rec['status'].upper()} — {rec.get('error')}")
    return records
