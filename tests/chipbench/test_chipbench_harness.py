"""The chip benchmark's harness on the CPU: cells resolve by name, new
files are found with no edit, the command refuses a CPU, the work counts
and peaks, and the trace reduction on a trace recorded on a TPU v5e."""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import chipbench_tiny  # noqa: F401  (puts the repo and src/ on sys.path)
from chipbench import harness, peaks, trace

ROOT = chipbench_tiny.ROOT
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "pcd_small.xplane.pb")


def bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("workload", [w["name"] for w in bench()["workloads"]])
def test_every_cell_resolves(workload):
    cell = harness.load_cell(workload)
    assert cell.config["name"] == next(
        w["config"] for w in bench()["workloads"] if w["name"] == workload
    )
    for key in ("chains", "steps", "schedule", "sample_every"):
        assert key in cell.traffic
    assert callable(cell.problem.make) and callable(cell.dynamics.step)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "spin_updates_per_s"}
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert callable(cell.readers[m["name"]].read)
    assert cell.extra["limits"], "every cell states the limits of its comparison"
    if cell.traffic.get("first_hit"):
        assert cell.target < 0


def test_paths_hold_every_file_the_cells_name():
    b = bench()
    for c in b["configs"]:
        assert c["file"].startswith("chipbench/")
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for p in b["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))


def _hashes(top: str) -> dict:
    out = {}
    for d, _, files in os.walk(top):
        for f in files:
            if "__pycache__" not in d:
                with open(os.path.join(d, f), "rb") as fh:
                    out[os.path.relpath(os.path.join(d, f), top)] = hashlib.sha256(fh.read()).hexdigest()
    return out


@pytest.mark.parametrize("added", ["config", "traffic", "metric"])
def test_new_files_are_found_without_editing_any(tmp_path, added):
    """A configuration, a traffic mix or a per-layer metric is a new file
    plus a new entry: no file that is there changes."""
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _hashes(str(tmp_path / "chipbench"))
    here = tmp_path / "chipbench"
    b = bench()
    cell = {"name": "new.cell", "config": "sk2048", "traffic": "anneal", "chips": 1,
            "why": "test"}
    if added == "config":
        cfg = json.loads((here / "configs" / "sk2048.json").read_text())
        cfg.update(name="sk1024", n=1024)
        (here / "configs" / "sk1024.json").write_text(json.dumps(cfg))
        b["configs"].append({"name": "sk1024", "source": "x", "reduced": [], "why": "test",
                             "file": "chipbench/configs/sk1024.json"})
        cell["config"] = "sk1024"
    elif added == "traffic":
        mix = json.loads((here / "traffic" / "pcd.json").read_text())
        mix.update(steps=4)
        (here / "traffic" / "pcd4.json").write_text(json.dumps(mix))
        cell["traffic"] = "pcd4"
    else:
        (here / "metrics" / "jobs_done.py").write_text(
            "def read(ctx):\n    return float(ctx.window.jobs)\n"
        )
        b["per_layer"].append({"name": "jobs_done", "unit": "jobs", "better": "higher",
                               "source": "host_clock", "layer": "run() host path",
                               "moves": "spin_updates_per_s", "workloads": ["new.cell"]})
    (here / "cells" / "new.cell.json").write_text(
        (here / "cells" / "sk2048.anneal.json").read_text()
    )
    b["workloads"].append(cell)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))

    loaded = harness.load_cell("new.cell", here=str(here))
    if added == "config":
        assert loaded.config["n"] == 1024
    elif added == "traffic":
        assert loaded.traffic["steps"] == 4
    else:
        assert "jobs_done" in loaded.readers
    after = _hashes(str(here))
    assert {k: v for k, v in after.items() if k in before} == before


BURST_LOOP = '''"""Bursts: `burst` jobs dispatched back to back, then all waited for."""
import time

import jax

from chipbench.window import Window

SPANS = ("dispatch", "block_wait")


def run_window(cell, job, inputs, seconds, span, checked):
    burst = int(cell.traffic["burst"])
    start_s, dispatch_s, latency_s, hits = [], [], [], []
    j, start = 0, time.perf_counter()
    while True:
        outs = []
        for _ in range(burst):
            key = jax.random.fold_in(inputs["jobs"], j + len(outs))
            t0 = time.perf_counter()
            with span("dispatch"):
                outs.append((t0, key, job(key, None)))
            dispatch_s.append(time.perf_counter() - t0)
        with span("block_wait"):
            jax.block_until_ready([o for _, _, o in outs])
        t2 = time.perf_counter()
        for t0, key, out in outs:
            start_s.append(t0 - start)
            latency_s.append(t2 - t0)
            hits.append(out.hit)
            checked.offer(j, (j, key, None, out))
            j += 1
        if t2 - start >= seconds:
            return Window(j, t2 - start, start_s, dispatch_s, latency_s, hits, checked.kept)
'''


def test_new_loop_and_run_args_plug_in_without_editing_any(tmp_path):
    """A job loop of another shape (`loops/<loop>.py`, named by a new mix)
    and a further `run()` argument made by a builder (`args/<build>.py`,
    named by a new cell) take only new files and entries; the cell runs
    through them and is correct."""
    from unittest import mock

    from repro.core import sampler_api

    here = chipbench_tiny.tree(str(tmp_path))
    before = _hashes(here)
    with open(os.path.join(here, "loops", "burst.py"), "w") as f:
        f.write(BURST_LOOP)
    os.makedirs(os.path.join(here, "args"), exist_ok=True)
    with open(os.path.join(here, "args", "two.py"), "w") as f:
        f.write("def build(spec, cell):\n    return 2\n")
    with open(os.path.join(here, "traffic", "anneal.json")) as f:
        mix = json.load(f)
    mix.update(loop="burst", burst=3)
    with open(os.path.join(here, "traffic", "anneal_burst.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(here, "cells", "sk2048.anneal.json")) as f:
        extra = json.load(f)
    extra["run_args"] = {"unroll": {"build": "two"}}
    with open(os.path.join(here, "cells", "sk2048.burst.json"), "w") as f:
        json.dump(extra, f)
    bench_path = os.path.join(os.path.dirname(here), "BENCHMARK.json")
    with open(bench_path) as f:
        b = json.load(f)
    b["workloads"].append({"name": "sk2048.burst", "config": "sk2048",
                           "traffic": "anneal_burst", "chips": 1, "why": "test"})
    with open(bench_path, "w") as f:
        json.dump(b, f)

    calls = []
    run = sampler_api.run

    def recorded(*a, **k):
        calls.append(k)
        return run(*a, **k)

    with mock.patch.object(sampler_api, "run", recorded):
        correct, readings, limits, result = chipbench_tiny.run(here, "sk2048.burst")
    assert correct, (readings, limits)
    assert result["attempted"] % 3 == 0 and result["attempted"] >= 3
    assert calls and all(k["unroll"] == 2 for k in calls)
    after = _hashes(here)
    assert {k: v for k, v in after.items() if k in before} == before


RELEARN_LOOP = '''"""Couplings changed between jobs, as a learning rule would."""
import time

import jax

from chipbench.window import Window

SPANS = ("dispatch",)
SHIFT = {"checked": 0.0}


def run_window(cell, job, inputs, seconds, span, checked):
    start_s, dispatch_s, latency_s, hits = [], [], [], []
    j, start = 0, time.perf_counter()
    while True:
        inst = {**inputs["inst"], "J": inputs["inst"]["J"] * (1.0 - 0.05 * j)}
        key = jax.random.fold_in(inputs["jobs"], j)
        t0 = time.perf_counter()
        with span("dispatch"):
            out = job(key, None, inst)
        t1 = time.perf_counter()
        jax.block_until_ready(out)
        t2 = time.perf_counter()
        start_s.append(t0 - start)
        dispatch_s.append(t1 - t0)
        latency_s.append(t2 - t0)
        hits.append(out.hit)
        told = {**inst, "J": inst["J"] * (1.0 + SHIFT["checked"])}
        checked.offer(j, (j, key, None, out, told))
        j += 1
        if j == 4:  # four jobs, each on other couplings, whatever `seconds`
            return Window(j, t2 - start, start_s, dispatch_s, latency_s, hits, checked.kept)
'''


@pytest.mark.parametrize("told", ["same", "other"])
def test_a_loop_may_change_the_instance_between_jobs(tmp_path, told):
    """A loop that changes the couplings before each job is checked job by
    job against the instance that job ran on: a sound run is correct, and
    one checked against other couplings than it ran on is not."""
    here = chipbench_tiny.tree(str(tmp_path))
    with open(os.path.join(here, "loops", "relearn.py"), "w") as f:
        f.write(RELEARN_LOOP.replace('"checked": 0.0', '"checked": 0.5' if told == "other"
                                     else '"checked": 0.0'))
    with open(os.path.join(here, "traffic", "anneal.json")) as f:
        mix = json.load(f)
    mix.update(loop="relearn")
    with open(os.path.join(here, "traffic", "relearn.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(here, "cells", "sk2048.anneal.json")) as f:
        extra = json.load(f)
    extra["traffic"]["check_jobs"] = 4
    with open(os.path.join(here, "cells", "sk2048.relearn.json"), "w") as f:
        json.dump(extra, f)
    bench_path = os.path.join(os.path.dirname(here), "BENCHMARK.json")
    with open(bench_path) as f:
        b = json.load(f)
    b["workloads"].append({"name": "sk2048.relearn", "config": "sk2048",
                           "traffic": "relearn", "chips": 1, "why": "test"})
    with open(bench_path, "w") as f:
        json.dump(b, f)
    correct, readings, limits, result = chipbench_tiny.run(here, "sk2048.relearn")
    assert correct == (told == "same"), (readings, limits)
    assert result["attempted"] == 4


def test_reference_refuses_a_run_argument_it_does_not_model(tmp_path):
    here = chipbench_tiny.tree(str(tmp_path))
    cell = harness.load_cell("maxcut3r4096.pcd", here=here)
    cell.run_args = {"diagnostics": True}
    inst = harness.make_instance(cell)
    with pytest.raises(ValueError, match="does not model run"):
        harness.reference_of(cell, inst, "full")


def test_command_exits_nonzero_on_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"), "--workload",
         "maxcut3r4096.pcd", "--seed", "3", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT,
    )
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_command_fails_with_only_the_benchmark_files(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    `paths`, the program is missing: the command fails and prints no
    result."""
    for p in bench()["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "maxcut3r4096.pcd", "--seed",
         "3", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env, cwd=tmp_path,
    )
    assert proc.returncode != 0
    assert "No module named 'repro'" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_work_counts_pinned():
    """Hand-computed least work for one shape of each kernel."""
    from chipbench.dynamics import colored_gibbs, tau_leap

    ops, nbytes = tau_leap.work({"n": 2048}, 128)
    assert ops == 2 * 2048 * 2048 * 128 == 1_073_741_824
    assert nbytes == 2048 * 2048 + 2 * 128 * 2048 == 4_718_592
    inst = {"nbr_idx": np.zeros((4096, 3), np.int32)}
    ops, nbytes = colored_gibbs.work(inst, 64)
    assert ops == 2 * 4096 * 3 * 64 == 1_572_864
    assert nbytes == 4096 * 3 * 8 + 2 * 64 * 4096 == 622_592
    # Both are bound by bytes on a TPU v5e: 4718592 / 819e9 s per step.
    least, bound = peaks.least_seconds(1_073_741_824, 4_718_592, "TPU v5 lite")
    assert bound == "bytes" and least == pytest.approx(5.7614e-6, rel=1e-4)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v99")
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_union_and_self_times():
    iv = np.array([[5.0, 7.0], [0.0, 2.0], [1.0, 3.0], [7.0, 8.0]])
    assert trace.union(iv).tolist() == [[0.0, 3.0], [5.0, 8.0]]
    assert trace.covered(trace.clip(trace.union(iv), 1.0, 6.0)) == 3.0
    ops = [("loop", 0.0, 10.0), ("a", 1.0, 3.0), ("b", 4.0, 8.0), ("a", 8.0, 9.0)]
    assert trace.self_times(ops) == {"loop": 3.0, "a": 3.0, "b": 4.0}


def test_trace_reduction_on_a_chip_trace():
    """A short traced window of maxcut3r4096.pcd, recorded on a TPU v5e;
    the numbers are recomputed here from the raw events."""
    tr = trace.load(FIXTURE)
    s = trace.summarize(tr)
    dispatch = [sp for sp in tr.spans if sp[0] == "dispatch"]
    lo, hi = dispatch[0][1], max(sp[2] for sp in tr.spans)
    assert s.window_s == pytest.approx((hi - lo) * 1e-9)
    # Busy: brute-force union of the programs on a 1 us grid.
    grid = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for a, b in tr.modules[0]:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            grid[int((a - lo) // 1000):int((b - lo) // 1000) + 1] = True
    assert s.busy_s == pytest.approx(grid.sum() * 1e-6, abs=len(tr.modules[0]) * 2e-6)
    assert 0 < s.busy_s < s.window_s
    kern = [(a, b) for n, a, b in tr.ops[0] if trace.KERNEL_MARK in n and a >= lo and b <= hi]
    assert kern and s.kernel_s == pytest.approx(sum(b - a for a, b in kern) * 1e-9)
    assert 0 < s.kernel_s <= s.busy_s
    assert s.device_ops[0][0].startswith("colored_gibbs_sweep")
    assert all(name in trace.SPANS + ("none",) for name, _ in s.idle_gaps)
    gaps = [g for _, g in s.idle_gaps]
    assert gaps == sorted(gaps, reverse=True)
    assert gaps[0] <= s.window_s - s.busy_s + 1e-12
