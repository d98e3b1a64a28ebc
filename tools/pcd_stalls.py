"""Where the 0.1–4 s stalls of single `maxcut3r4096.pcd` jobs fall.

Runs untraced windows of the benchmark's pcd cell in one process (on a TPU;
elsewhere at the CPU tests' cut size). For each job over 2.5 times the
median latency it gives the job's `run()` phases from
`repro.core.tracing.recent()`. Beside the jobs, a watchdog thread wakes
every 5 ms and notes each wake over 30 ms late, and writes down the main
thread's innermost stack frames once a job is 60 ms overdue. A stall that
delays the watchdog as much as the job stopped the whole process, not only
the thread that waits on the chip.

    python3 tools/pcd_stalls.py [--seconds S] [--reps N] [--out FILE]

Prints one JSON line per window, and writes them all to `--out` if given.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import threading
import time
import traceback

ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
ap.add_argument("--seconds", type=float, default=40.0)
ap.add_argument("--reps", type=int, default=2)
ap.add_argument("--out")
args = ap.parse_args()
out_path = os.path.abspath(args.out) if args.out else None
root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [root, os.path.join(root, "src")]
os.chdir(root)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import harness, window as window_mod  # noqa: E402
from repro.core import tracing  # noqa: E402

if jax.devices()[0].platform == "tpu":
    jax.config.update("jax_compilation_cache_dir", os.environ.get(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(root, ".jax_cache")))
    here = harness.HERE
else:
    sys.path.insert(0, os.path.join(root, "tests", "chipbench"))
    import chipbench_tiny

    harness.check_kernel = lambda *a: None
    here = chipbench_tiny.tree(tempfile.mkdtemp())
cell = harness.load_cell("maxcut3r4096.pcd", here=here)
inst = harness.make_instance(cell)
inputs = harness.seed_inputs(cell, inst, 1618033988)
job0 = harness.prepare(cell, inst, inputs)
state = {"job_start": None, "stop": False, "t0": 0.0}
main_id = threading.get_ident()
late, stacks = [], []


def job(key, s_in):
    state["job_start"] = time.perf_counter()
    return job0(key, s_in)


def watchdog():
    dumped_for = None
    while not state["stop"]:
        t = time.perf_counter()
        time.sleep(0.005)
        now = time.perf_counter()
        if now - t - 0.005 > 0.03:
            late.append([t - state["t0"], now - t - 0.005])
        js = state["job_start"]
        if js is not None and now - js > 0.06 and dumped_for != js:
            dumped_for = js
            frame = sys._current_frames().get(main_id)
            stack = traceback.extract_stack(frame)[-6:] if frame else []
            stacks.append([js - state["t0"], now - js,
                           [f"{os.path.basename(f.filename)}:{f.lineno} {f.name}" for f in stack]])


out = {}
for rep in range(args.reps):
    late.clear()
    stacks.clear()
    state.update(t0=time.perf_counter(), stop=False)
    th = threading.Thread(target=watchdog, daemon=True)
    th.start()
    gc.collect()
    gc.disable()
    try:
        w = cell.loop.run_window(cell, job, inputs, args.seconds, window_mod.Spans(False),
                                 window_mod.Checked(0, 1))
    finally:
        gc.enable()
        state["stop"] = True
        th.join()
    recs = tracing.recent(w.jobs)
    assert len(recs) == w.jobs
    lat = np.asarray(w.latency_s)
    med = float(np.median(lat))
    slow = [[int(i), w.start_s[i], 1e3 * w.dispatch_s[i], 1e3 * lat[i],
             *(1e-6 * getattr(recs[i], f) for f in ("run_ns", "validate_ns", "prep_ns", "call_ns"))]
            for i in np.flatnonzero(lat > 2.5 * med)]
    out[f"rep{rep}"] = {
        "jobs": w.jobs, "median_latency_ms": 1e3 * med,
        "slow[i,start_s,dispatch_ms,latency_ms,run,validate,prep,call_ms]": slow,
        "watchdog_late[t_s,late_s]": late[:40], "overdue_stacks": stacks[:40],
    }
    print(json.dumps(out[f"rep{rep}"]), flush=True)
if out_path:
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
