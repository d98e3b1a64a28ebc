"""A copy of the benchmark's files at a size the CPU test run can hold.

The copy keeps every file of `chipbench/` and `BENCHMARK.json`, with the
configurations cut to n = 256 (SK) and n = 512 (3-regular MaxCut) and the
jobs to 8 chains of 20 steps recorded every 5. The limits of the
comparison are the cells' own.
"""
from __future__ import annotations

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SIZES = {"sk2048": 256, "maxcut3r4096": 512}
TARGETS = {"sk2048.anneal": -0.55, "maxcut3r4096.anneal": -1.1}
ANNEAL = {"chains": 8, "steps": 20, "sample_every": 5, "check_jobs": 2}
PCD = {"chains": 8, "check_jobs": 4}


def _edit(path: str, change) -> None:
    with open(path) as f:
        data = json.load(f)
    change(data)
    with open(path, "w") as f:
        json.dump(data, f)


def tree(dest: str) -> str:
    """Write the cut copy under `dest`; return its `chipbench` directory."""
    shutil.copytree(os.path.join(ROOT, "chipbench"), os.path.join(dest, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), os.path.join(dest, "BENCHMARK.json"))
    here = os.path.join(dest, "chipbench")
    for name, n in SIZES.items():
        _edit(os.path.join(here, "configs", name + ".json"), lambda c, n=n: c.update(n=n))
    for cell, target in TARGETS.items():
        def cut(c, target=target):
            c["traffic"] = {**c.get("traffic", {}), **ANNEAL}
            c["target_energy_per_spin"] = target
        _edit(os.path.join(here, "cells", cell + ".json"), cut)
    _edit(os.path.join(here, "cells", "maxcut3r4096.pcd.json"),
          lambda c: c.update(traffic={**c.get("traffic", {}), **PCD}))
    return here


def run(here: str, workload: str, seed: int = 2**33 + 5, job_kind: str = "program"):
    """One untraced run of a cut cell on the CPU. The Pallas kernels run in
    interpret mode here, so the check that the run holds a compiled kernel
    is stepped over.

    Returns (correct, readings, limits, result line or None)."""
    import math
    import time
    from unittest import mock

    import jax

    from chipbench import harness

    jax.clear_caches()  # a planted fault must not hide behind a compiled program
    cell = harness.load_cell(workload, here=here)
    with mock.patch.object(harness, "check_kernel", lambda *a: None):
        if job_kind == "program":
            result = harness.run_cell(cell, seed, 0.3, False, time.perf_counter())
            readings = {k: v["value"] for k, v in result["checks"].items()}
            return result["correct"], readings, cell.extra["limits"], result
        inst = harness.make_instance(cell)
        inputs = harness.seed_inputs(cell, inst, seed)
        job = harness.prepare(cell, inst, inputs, job_kind)
        readings = harness.measure(cell, inst, job, inputs, seed, 0.3).readings
    limits = cell.extra["limits"]
    correct = all(math.isfinite(v) and v <= limits[k] for k, v in readings.items())
    return correct, readings, limits, None
