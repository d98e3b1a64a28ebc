"""The benchmark's harness: cells found by name, the timed window, the check.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric lives in a file of its own, found by the name that
`BENCHMARK.json` gives it:

  configs/<config>.json     sizes, instance seed, problem and kernel names
                            (the `file` of the configuration's entry);
  problems/<problem>.py     the yardstick's instance, its energy, and the
                            same instance handed to the program;
  dynamics/<dynamics>.py    the reference step and the kernel's least work
                            (the configuration's `dynamics`, or the cell's);
  traffic/<traffic>.json    the job loop's parameters;
  loops/<loop>.py           the job loop that the mix names (`window.py`);
  cells/<workload>.json     what one cell adds: traffic overrides, further
                            `run_args`, another `dynamics`, the first-hit
                            target, the limits of the comparison;
  metrics/<metric>.py       the reader of one per-layer metric;
  args/<build>.py           the builder of a `run()` argument that is no
                            plain JSON value (see `run_args`).

A job is one call of `sampler_api.run(..., backend="pallas")`, with the
configuration's and the cell's `run_args` as further keyword arguments.
The program is imported only by `program_job`, the builders and the
problems' `program_problem`.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import tempfile
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import peaks as peaks_mod
from chipbench import reference
from chipbench import trace as trace_mod
from chipbench import window as window_mod

HERE = os.path.dirname(os.path.abspath(__file__))

def load_module(path: str):
    """Import the Python file at `path` (its name may hold dots)."""
    name = "chipbench_" + os.path.relpath(path, HERE).replace(os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_json(path: str) -> dict:
    """The JSON object in `path`."""
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One workload of `BENCHMARK.json`, with every file it names loaded."""

    name: str
    here: str                   # the directory its files were found in
    chips: int
    config: dict
    traffic: dict
    extra: dict                 # cells/<workload>.json, or {}
    problem: object             # problems/<problem>.py
    dynamics: object            # dynamics/<dynamics>.py
    loop: object                # loops/<loop>.py
    run_args: dict              # further keyword arguments of run(), as specs
    end_to_end: list[dict]
    per_layer: list[dict]
    readers: dict[str, object]  # metric name -> metrics/<name>.py

    @property
    def target(self) -> Optional[float]:
        """First-hit energy target of the cell's jobs, or None."""
        if not self.traffic.get("first_hit"):
            return None
        return float(self.extra["target_energy_per_spin"]) * int(self.config["n"])


def load_cell(workload: str, bench_path: Optional[str] = None, here: str = HERE) -> Cell:
    """Resolve `workload` to its configuration, traffic, cell and metric files."""
    bench = read_json(bench_path or os.path.join(os.path.dirname(here), "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; have {sorted(cells)}")
    entry = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    config = read_json(os.path.join(os.path.dirname(here), cfg_entry["file"]))
    traffic = read_json(os.path.join(here, "traffic", entry["traffic"] + ".json"))
    cell_path = os.path.join(here, "cells", workload + ".json")
    extra = read_json(cell_path) if os.path.exists(cell_path) else {}
    traffic = {**traffic, **extra.get("traffic", {})}
    run_args = {**config.get("run_args", {}), **extra.get("run_args", {})}
    e2e = [m for m in bench["end_to_end"] if workload in m.get("workloads", [workload])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [
        m for m in bench["per_layer"]
        if (workload in m["workloads"] if "workloads" in m else m["moves"] in e2e_names)
    ]
    readers = {
        m["name"]: load_module(os.path.join(here, "metrics", m["name"] + ".py"))
        for m in per_layer
    }
    return Cell(
        name=workload,
        here=here,
        chips=int(entry["chips"]),
        config=config,
        traffic=traffic,
        extra=extra,
        problem=load_module(os.path.join(here, "problems", config["problem"] + ".py")),
        dynamics=load_module(os.path.join(
            here, "dynamics", extra.get("dynamics", config["dynamics"]) + ".py")),
        loop=load_module(os.path.join(here, "loops", traffic["loop"] + ".py")),
        run_args=run_args,
        end_to_end=e2e,
        per_layer=per_layer,
        readers=readers,
    )


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from every bit of a seed of any size."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------


def build_run_args(cell: Cell) -> dict:
    """The cell's further `run()` arguments as the program takes them: a
    JSON value as it is; an object with a `build` key made by
    `args/<build>.py`'s `build(spec, cell)`."""
    out = {}
    for name, spec in cell.run_args.items():
        if isinstance(spec, dict) and "build" in spec:
            builder = load_module(os.path.join(cell.here, "args", spec["build"] + ".py"))
            spec = builder.build(spec, cell)
        out[name] = spec
    return out


def program_job(cell: Cell, problem) -> tuple[Callable, Callable]:
    """(job, call): `job(key, s0, inst=None)` runs one job through the
    program on `problem`, or on the instance `inst` that a loop changed,
    and returns its `Outputs`; `call(problem, key, s0)` is the bare `run()`
    call, for lowering."""
    from repro.core import sampler_api

    t = cell.traffic
    kernel = sampler_api.get_kernel(cell.config["kernel"], **cell.config.get("kernel_args", {}))
    sched = dict(t["schedule"])
    schedule = getattr(sampler_api, sched.pop("kind"))(**sched)
    target = cell.target
    run_args = build_run_args(cell)

    def call(problem, key, s0):
        return sampler_api.run(
            problem, kernel, key, n_steps=t["steps"], s0=s0, n_chains=t["chains"],
            schedule=schedule, sample_every=t["sample_every"], first_hit=target,
            backend="pallas", **run_args,
        )

    def job(key, s0, inst=None):
        res = call(problem if inst is None else cell.problem.program_problem(inst), key, s0)
        out = reference.Outputs(s=res.s)
        if t["sample_every"]:
            out.samples, out.energies = res.samples, res.energies
        if target is not None:
            out.hit, out.t_hit = res.hit, res.t_hit
        return out

    return job, call


def control_job(cell: Cell, inst: dict) -> Callable:
    """The reference in the control precision, in the program's place."""
    ref = reference_of(cell, inst, "control")

    def job(key, s0, job_inst=None):
        r = ref if job_inst is None else reference_of(cell, job_inst, "control")
        return r.simulate(key, s0)

    return job


def reference_of(cell: Cell, inst: dict, prec: str) -> reference.Reference:
    """The cell's plain reference in precision `prec`."""
    return reference.Reference(
        cell.problem, cell.dynamics, cell.config, inst, cell.traffic, cell.target,
        cell.run_args, prec,
    )


# ---------------------------------------------------------------------------
# The end-to-end metrics of a window
# ---------------------------------------------------------------------------


def e2e_metrics(cell: Cell, win: window_mod.Window) -> dict:
    """The cell's end-to-end metrics other than `setup_s`."""
    t = cell.traffic
    chains_done = win.jobs * t["chains"]
    values = {
        "spin_updates_per_s": chains_done * t["steps"] * int(cell.config["n"]) / win.window_s,
    }
    if win.hits:
        hits = int(sum(int(np.asarray(h).sum()) for h in win.hits))
        # No hit at all reads as half a hit, so the number stays finite.
        p = max(hits, 0.5) / chains_done
        tau = win.window_s / chains_done
        values["tts99_s"] = tau * (1.0 if p >= 1.0 else max(1.0, math.log(0.01) / math.log1p(-p)))
    return values


# ---------------------------------------------------------------------------
# Set-up, the check, and one whole run
# ---------------------------------------------------------------------------


def make_instance(cell: Cell) -> dict:
    """The configuration's instance, drawn from its own fixed seed."""
    return cell.problem.make(cell.config, int(cell.config["instance_seed"]))


def seed_inputs(cell: Cell, inst: dict, seed: int) -> dict:
    """Everything a run draws from `seed`: the warm-up and job keys, and the
    first states of a mix that carries its chains from job to job; with the
    instance the run starts from."""
    k_warm, k_jobs, k_s0 = jax.random.split(seed_key(seed), 3)
    t = cell.traffic
    s_first = None
    if t.get("carry_states"):
        s_first = reference.random_states(jax.random.split(k_s0, t["chains"]), inst["n"])
    return {"warm": k_warm, "jobs": k_jobs, "s_first": s_first, "inst": inst}


def check(cell: Cell, inst: dict, win: window_mod.Window) -> tuple[dict, int]:
    """Readings of the checked jobs against the full-precision reference of
    the instance each ran on, and how many checked jobs read above a limit
    on their own."""
    refs = {}
    limits = cell.extra["limits"]
    counts, failed = [], 0
    for _, key, s_in, out, *job_inst in sorted(win.kept, key=lambda k: k[0]):
        job_inst = job_inst[0] if job_inst else inst
        if id(job_inst) not in refs:
            refs[id(job_inst)] = reference_of(cell, job_inst, "full")
        c = refs[id(job_inst)].compare(key, s_in, out)
        counts.append(c)
        if any(v > limits[k] for k, v in reference.readings([c]).items()):
            failed += 1
    return reference.readings(counts), failed


def device_info() -> dict:
    """The device as JAX reports it, with the fullest chip's peak memory."""
    devices = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(peak),
    }


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read."""

    cell: Cell
    window: window_mod.Window
    trace: Optional[trace_mod.Summary]
    device_kind: str
    inst: dict

    def roofline(self, kernel: str) -> Optional[float]:
        """Share (%) of its roofline that `kernel` reached, or None where the
        cell runs another kernel or the trace shows none of it."""
        if self.cell.dynamics.KERNEL != kernel or not self.trace or self.trace.kernel_s <= 0:
            return None
        t = self.cell.traffic
        ops, nbytes = self.cell.dynamics.work(self.inst, t["chains"])
        steps = self.window.jobs * t["steps"]
        least, _ = peaks_mod.least_seconds(ops * steps, nbytes * steps, self.device_kind)
        return 100.0 * least / self.trace.kernel_s


def check_kernel(call: Callable, problem, inputs: dict) -> None:
    """The program's run must hold a Pallas kernel (`tpu_custom_call`), so
    that nothing runs in interpret mode."""
    lowered = jax.jit(call).lower(problem, inputs["warm"], inputs["s_first"])
    if "tpu_custom_call" not in lowered.as_text():
        raise RuntimeError("the run program holds no tpu_custom_call: no Pallas kernel")


def prepare(cell: Cell, inst: dict, inputs: dict, job_kind: str = "program",
            phases: Optional[dict] = None) -> Callable:
    """The job function of `job_kind` ("program", whose run must hold a
    Pallas kernel, or "control"), warmed up on the cell's own shapes. The
    seconds of building and of the warm-up job go into `phases`."""
    phases = {} if phases is None else phases
    t0 = time.perf_counter()
    if job_kind == "program":
        problem = cell.problem.program_problem(inst)
        job, call = program_job(cell, problem)
        check_kernel(call, problem, inputs)
    else:
        job = control_job(cell, inst)
    t1 = time.perf_counter()
    warm = job(jax.random.fold_in(inputs["warm"], 0), inputs["s_first"])
    jax.block_until_ready(warm)
    if cell.traffic.get("states_to_host"):
        np.asarray(warm.s)
    phases["build_s"] = t1 - t0
    phases["warm_job_s"] = time.perf_counter() - t1
    return job


@dataclasses.dataclass
class Measured:
    """What `measure` hands back."""

    window: window_mod.Window
    trace: Optional[trace_mod.Summary]
    device: dict
    readings: dict
    failed: int
    compiles: dict      # counts of the window
    host: dict          # the host's counters over the window


def measure(cell: Cell, inst: dict, job: Callable, inputs: dict, seed: int,
            seconds: float, traced: bool = False,
            compiles: Optional[window_mod.Compiles] = None) -> Measured:
    """The window, then the device's peak memory, then the check."""
    compiles = window_mod.Compiles() if compiles is None else compiles
    log_dir = None
    if traced:
        log_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    checked = window_mod.Checked(int(cell.traffic["check_jobs"]), seed)
    # As `timeit` does: collect now and keep the collector out of the
    # window, so that no collector pause lands inside it.
    gc.collect()
    gc.disable()
    compiles.phase = "window"
    before = window_mod.host_counters()
    try:
        win = cell.loop.run_window(
            cell, job, inputs, seconds, window_mod.Spans(traced), checked
        )
    finally:
        host = window_mod.counter_delta(before, window_mod.host_counters())
        compiles.phase = "after"
        gc.enable()
        if traced:
            jax.profiler.stop_trace()
    device = device_info()
    summary = None
    if traced:
        try:
            summary = trace_mod.summarize(
                trace_mod.load(trace_mod.find_xplane(log_dir), cell.loop.SPANS)
            )
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
    win.hits = [np.asarray(h) for h in win.hits]
    readings, failed = check(cell, inst, win)
    return Measured(win, summary, device, readings, failed, compiles.of("window"), host)


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, t_start: float,
             phases: Optional[dict] = None) -> dict:
    """One run of a cell: set-up, the window, the check. Returns the result
    line's object; `setup_s` runs from `t_start` to the first timed job.
    `phases` holds the seconds of the set-up before this call (imports,
    the backend), and gets those of the rest."""
    phases = {} if phases is None else phases
    compiles = window_mod.Compiles()
    t0 = time.perf_counter()
    inst = make_instance(cell)
    inputs = seed_inputs(cell, inst, seed)
    phases["instance_s"] = time.perf_counter() - t0
    job = prepare(cell, inst, inputs, "program", phases)
    setup_s = time.perf_counter() - t_start
    setup_compiles = compiles.of("setup")
    m = measure(cell, inst, job, inputs, seed, seconds, traced, compiles)
    win, summary, device = m.window, m.trace, m.device
    limits = cell.extra["limits"]
    correct = all(math.isfinite(v) and v <= limits[k] for k, v in m.readings.items())
    units = {x["name"]: x["unit"] for x in cell.end_to_end + cell.per_layer}
    metrics = {}
    if traced:
        ctx = Context(cell, win, summary, device["kind"], inst)
        for x in cell.per_layer:
            value = cell.readers[x["name"]].read(ctx)
            if value is not None:
                metrics[x["name"]] = {"value": value, "unit": units[x["name"]]}
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    else:
        values = {**e2e_metrics(cell, win), "setup_s": setup_s}
        for x in cell.end_to_end:
            metrics[x["name"]] = {"value": values[x["name"]], "unit": units[x["name"]]}
    result = {
        "correct": correct, "attempted": win.jobs, "failed": m.failed,
        "metrics": metrics, "device": device,
    }
    if traced:
        result["breakdown"] = {"device_ops": summary.device_ops, "idle_gaps": summary.idle_gaps}
    result["setup"] = {**phases, "compiles": setup_compiles}
    lat = np.asarray(win.latency_s)
    slow = np.flatnonzero(lat > 1.5 * np.median(lat))[:20]
    result["window"] = {
        "window_s": win.window_s, "median_latency_s": float(np.median(lat)),
        # [index, start in the window, dispatch, latency] of jobs over 1.5x the median
        "slow_jobs": [[int(i), win.start_s[i], win.dispatch_s[i], float(lat[i])] for i in slow],
        "compiles": m.compiles,
        "host": m.host,
    }
    result["checks"] = {k: {"value": v, "limit": limits[k]} for k, v in m.readings.items()}
    return result
