"""What every job loop shares: the host spans, the sample of checked jobs,
the window's record, and the counts of what else the process did meanwhile.

A job loop is `loops/<loop>.py`, named by a traffic mix's `loop`. It has
`SPANS`, the names of the host spans it opens with `span(name)` (one of
them `dispatch`, around each call of a job: the traced window starts at
the first), and `run_window(cell, job, inputs, seconds, span, checked)
-> Window`: it calls `job(key, s_in)` until `seconds` have passed, waits
for the last, records every job in the `Window` and offers each to
`checked` as (index, key, s_in, Outputs). A loop that changes the
instance between jobs calls `job(key, s_in, inst)` with the changed
instance dict (`problems/<problem>.make`'s form) and offers (index, key,
s_in, Outputs, inst): each checked job is compared with the reference of
its own instance. `inputs["inst"]` is the instance the run starts from.
"""
from __future__ import annotations

import dataclasses
import os
import resource
import time
from contextlib import contextmanager

import jax
import numpy as np


class Spans:
    """The harness's host spans: kept on the host clock and, while a trace
    is taken, written into it with `TraceAnnotation`."""

    def __init__(self, traced: bool):
        self.traced = traced

    @contextmanager
    def __call__(self, name: str):
        if self.traced:
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield


class Checked:
    """A seeded, uniform sample of `slots` jobs of a window, for the check.

    Reservoir sampling from a generator seeded by the run's seed, so every
    job is equally likely to be checked however many jobs the window holds.
    """

    def __init__(self, slots: int, seed: int):
        self.slots = slots
        self.rng = np.random.default_rng(seed)
        self.kept = []              # (job index, key, s_in, Outputs[, instance])

    def offer(self, j: int, item) -> None:
        """Keep job `j`'s `item` with the reservoir's odds."""
        slot = j if j < self.slots else int(self.rng.integers(0, j + 1))
        if slot < self.slots:
            if slot < len(self.kept):
                self.kept[slot] = item
            else:
                self.kept.append(item)


@dataclasses.dataclass
class Window:
    """What a job loop hands back."""

    jobs: int
    window_s: float
    start_s: list[float]        # per job, from the window's start
    dispatch_s: list[float]     # per job, the host time of the call
    latency_s: list[float]      # per job, the call to its results being ready
    hits: list                  # per job, the (B,) first-hit flags, if tracked
    kept: list                  # Checked.kept


# ---------------------------------------------------------------------------
# What else the process did: compilations, and the host's own counters
# ---------------------------------------------------------------------------

COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "traces",
    "/jax/core/compile/backend_compile_duration": "executables",
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


class Compiles:
    """Counts of JAX's traces, executables built or loaded, and persistent
    cache hits and misses, by the phase of the run they fall in."""

    _active = None

    def __init__(self):
        self.phase = "setup"
        self.counts: dict[str, dict] = {}
        if Compiles._active is None:
            jax.monitoring.register_event_listener(Compiles._event)
            jax.monitoring.register_event_duration_secs_listener(Compiles._duration)
        Compiles._active = self

    def _add(self, event: str, seconds: float) -> None:
        name = COMPILE_EVENTS.get(event)
        if name is not None:
            c = self.counts.setdefault(self.phase, {v: 0 for v in COMPILE_EVENTS.values()})
            c[name] += 1
            if name == "executables":
                c["executable_s"] = c.get("executable_s", 0.0) + seconds

    @staticmethod
    def _event(event: str, **_):
        if Compiles._active is not None:
            Compiles._active._add(event, 0.0)

    @staticmethod
    def _duration(event: str, seconds: float, **_):
        if Compiles._active is not None:
            Compiles._active._add(event, seconds)

    def of(self, phase: str) -> dict:
        """The counts of `phase`, zeros where nothing happened."""
        return self.counts.get(phase, {v: 0 for v in COMPILE_EVENTS.values()})


def host_counters() -> dict:
    """Counters of the host and of this process that a stall would move:
    CPU time stolen by the hypervisor and spent waiting on I/O (all CPUs,
    seconds), memory compaction stalls, this process's CPU time, major
    page faults and involuntary context switches."""
    out = {"wall_s": time.perf_counter()}
    tick = os.sysconf("SC_CLK_TCK")
    try:
        with open("/proc/stat") as f:
            cpu = f.readline().split()
        out["iowait_s"] = int(cpu[5]) / tick
        out["steal_s"] = int(cpu[8]) / tick
    except (OSError, IndexError, ValueError):
        pass
    try:
        with open("/proc/vmstat") as f:
            for line in f:
                name, value = line.split()
                if name == "compact_stall":
                    out["compact_stalls"] = int(value)
    except (OSError, ValueError):
        pass
    ru = resource.getrusage(resource.RUSAGE_SELF)
    out.update(cpu_s=ru.ru_utime + ru.ru_stime, major_faults=ru.ru_majflt,
               involuntary_switches=ru.ru_nivcsw)
    return out


def counter_delta(before: dict, after: dict) -> dict:
    """`after - before` of each counter both hold."""
    return {k: after[k] - before[k] for k in after if k in before}
