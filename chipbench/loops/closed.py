"""The closed loop: one client runs jobs back to back, each waited for.

Traffic parameters read here: `carry_states` (each job starts from the
previous job's final states, which stay on the device; the first from
the run's seeded states) and `states_to_host` (each job's final states
are copied to the host before the next job starts, and count in its
latency). The last job is waited for, so the window ends with it.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from chipbench.window import Window

SPANS = ("dispatch", "block_wait", "to_host", "next_job")


def run_window(cell, job, inputs: dict, seconds: float, span, checked) -> Window:
    """Run jobs one after another for `seconds`, the job keys folded from
    `inputs["jobs"]`."""
    t = cell.traffic
    carry, to_host = t.get("carry_states", False), t.get("states_to_host", False)
    s_in = inputs["s_first"] if carry else None
    # The host copy of the job's input, kept for the check.
    s_in_host = None if s_in is None else np.asarray(s_in)
    start_s, dispatch_s, latency_s, hits = [], [], [], []
    j = 0
    start = time.perf_counter()
    while True:
        with span("next_job"):
            key = jax.random.fold_in(inputs["jobs"], j)
        t0 = time.perf_counter()
        with span("dispatch"):
            out = job(key, s_in)
        t1 = time.perf_counter()
        with span("block_wait"):
            jax.block_until_ready(out)
        s_host = None
        if to_host:
            with span("to_host"):
                s_host = np.asarray(out.s)
        t2 = time.perf_counter()
        start_s.append(t0 - start)
        dispatch_s.append(t1 - t0)
        latency_s.append(t2 - t0)
        with span("next_job"):
            if out.hit is not None:
                hits.append(out.hit)
            handed = out if s_host is None else dataclasses.replace(out, s=s_host)
            # passlint: ignore[PASS001] the check replays the job with the job's own key
            checked.offer(j, (j, key, s_in if s_in_host is None else s_in_host, handed))
            if carry:
                s_in, s_in_host = out.s, s_host
            j += 1
        if t2 - start >= seconds:
            break
    return Window(j, t2 - start, start_s, dispatch_s, latency_s, hits, checked.kept)
