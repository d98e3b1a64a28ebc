"""Where a `maxcut3r4096.pcd` job's host time and device idle time go, by
the phases of `sampler_api.run()`.

Runs the benchmark's pcd cell in one process (on a TPU; elsewhere at the CPU
tests' cut size): two untraced windows, then a traced one whose device idle
gaps are each named by the innermost host span over them, the harness's
spans and `repro.core.tracing.SPANS` alike; device programs are counted per
job and per phase. Then the cost of the spans themselves: an empty `run()`
shell of four spans, timed with the profiler off and on.

    python3 tools/pcd_phases.py [--root DIR] [--seconds S] [--out FILE]

`--root` is a checkout to measure (default: this one); one without
`repro.core.tracing` reports host times and gaps by the harness's spans
only. Prints one JSON object, and writes it to `--out` if given.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time

ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
ap.add_argument("--root", default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ap.add_argument("--seconds", type=float, default=10.0)
ap.add_argument("--out")
args = ap.parse_args()
out_path = os.path.abspath(args.out) if args.out else None
root = os.path.abspath(args.root)
sys.path[:0] = [root, os.path.join(root, "src")]
os.chdir(root)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import harness, trace as trace_mod, window as window_mod  # noqa: E402

try:
    from repro.core import tracing
except ImportError:
    tracing = None

on_tpu = jax.devices()[0].platform == "tpu"
if on_tpu:
    jax.config.update("jax_compilation_cache_dir", os.environ.get(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(root, ".jax_cache")))
    cell = harness.load_cell("maxcut3r4096.pcd")
else:
    sys.path.insert(0, os.path.join(root, "tests", "chipbench"))
    import chipbench_tiny

    harness.check_kernel = lambda *a: None
    cell = harness.load_cell("maxcut3r4096.pcd", here=chipbench_tiny.tree(tempfile.mkdtemp()))
inst = harness.make_instance(cell)
inputs = harness.seed_inputs(cell, inst, 3141592653)
job = harness.prepare(cell, inst, inputs)
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
out = {"root": root, "platform": jax.devices()[0].platform}


def window(traced: bool):
    gc.collect()
    gc.disable()
    try:
        return cell.loop.run_window(cell, job, inputs, args.seconds, window_mod.Spans(traced),
                                    window_mod.Checked(0, 1))
    finally:
        gc.enable()


def phase_ms(jobs: int):
    """Mean span durations of the window's `run()` calls, ms."""
    recs = tracing.recent(jobs) if tracing else []
    if len(recs) != jobs:
        return None
    return {f: 1e-6 * float(np.mean([getattr(r, f) for r in recs]))
            for f in ("run_ns", "validate_ns", "prep_ns", "call_ns")}


def host(w) -> dict:
    return {"jobs": w.jobs, "dispatch_ms_mean": 1e3 * float(np.mean(w.dispatch_s)),
            "latency_ms_median": 1e3 * float(np.median(w.latency_s)),
            "phases_ms": phase_ms(w.jobs)}


def innermost(spans, a: int, b: int) -> str:
    """The span over most of the gap [a, b]; of those over half of it, the
    shortest (the innermost)."""
    best = None
    for name, s, e in spans:
        ov = min(e, b) - max(s, a)
        if ov <= 0:
            continue
        half = ov >= 0.5 * (b - a)
        key = (not half, e - s if half else -ov)
        if best is None or key < best[0]:
            best = (key, name)
    return best[1] if best else "none"


for rep in range(2):
    out[f"untraced{rep}"] = host(window(False))

log_dir = tempfile.mkdtemp()
jax.profiler.start_trace(log_dir, profiler_options=opts)
w = window(True)
jax.profiler.stop_trace()
res = out["traced"] = host(w)
if on_tpu:
    names = cell.loop.SPANS + (tracing.SPANS if tracing else ())
    tr = trace_mod.load(trace_mod.find_xplane(log_dir), names)
    lo = min(s for n, s, _ in tr.spans if n == "dispatch")
    hi = max(e for _, _, e in tr.spans)
    busy = trace_mod.clip(trace_mod.union(tr.modules[0]), lo, hi)
    edges = np.concatenate([[lo], busy.reshape(-1), [hi]]).reshape(-1, 2)
    named = [(innermost(tr.spans, a, b), 1e-9 * (b - a)) for a, b in edges if b > a]
    idle = {}
    for n, g in named:
        idle[n] = idle.get(n, 0.0) + g
    res["idle_s_by_innermost_span"] = dict(sorted(idle.items(), key=lambda kv: -kv[1]))
    res["gaps_over_0.1s"] = [x for x in named if x[1] >= 0.1]
    starts = [m[0] for m in tr.modules[0] if lo <= m[0] <= hi]
    res["device_programs_per_job"] = len(starts) / w.jobs
    for nm in (tracing.SPANS[1:] if tracing else ()):
        sp = [(a, b) for n, a, b in tr.spans if n == nm]
        res[f"device_programs_in_{nm}_per_job"] = sum(
            1 for t in starts for a, b in sp if a <= t <= b) / max(1, len(sp))

if tracing:
    def shell():
        with tracing.call() as span:
            for name in tracing.SPANS[1:]:
                with span(name):
                    pass

    for key, n, traced in (("instrumentation_off_us", 20000, False),
                           ("instrumentation_on_us", 5000, True)):
        if traced:
            jax.profiler.start_trace(tempfile.mkdtemp(), profiler_options=opts)
        t0 = time.perf_counter()
        for _ in range(n):
            shell()
        out[key] = (time.perf_counter() - t0) / n * 1e6
        if traced:
            jax.profiler.stop_trace()

print(json.dumps(out))
if out_path:
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
