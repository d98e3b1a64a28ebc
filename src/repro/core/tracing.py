"""Host spans and per-call records of `sampler_api.run()`.

Every `run()` call that really runs opens the spans of `SPANS`:

    run           the whole call;
    run.validate  kernel lookup, problem-kind and backend checks, fault
                  validation and binding, the finite-energy probe and its
                  read to the host;
    run.prep      beta schedule, first-hit target, unroll, per-chain keys;
    run.call      the jitted sampling program's call (both passes under
                  `timeit=True`).

Each span is a `jax.profiler.TraceAnnotation(name, call=<id>)`: while a
profiler trace is taken it lands in the host plane on the device trace's
clock, and the spans of one call share its `call` stat. Each is also timed
with `time.perf_counter_ns()` into the call's record.

`recent(k)` returns the last `k` records, oldest first; the last `KEEP`
calls are kept. A `run()` traced by an outer transformation (the jitted
tempering loop) times tracing, not running, and leaves no span and no
record. A call that raises leaves no record.

Row occupancy. A Pallas kernel whose rows are chains notes, each time a
program calling it is traced, the rows that carry a chain per call, the
rows the MXU processes per call (those padded to its row tile) and the
calls per step; `row_occupancy(kernel)` reads the last note. A program
served from JAX's caches is not traced again, so the note is that of the
last program traced, which is the one running wherever one program is
traced and then called (the benchmark's cells). `run()` on `n_chains`
chains of dense tau-leap reads `n_chains` of `n_chains` rounded up to 128
rows in one call per step, where it read 1 of 128 rows in `n_chains`.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import time
from typing import NamedTuple, Optional

import jax

SPANS = ("run", "run.validate", "run.prep", "run.call")
# Calls kept: a fixed bound on the record's memory (a few MB), whatever
# the process runs; older calls are dropped first.
KEEP = 16384


class CallRecord(NamedTuple):
    """One `run()` call: span durations in ns on the host clock."""

    call: int          # the call's id: the `call` stat of its spans
    start_ns: int      # `perf_counter_ns()` at the start of `run`
    run_ns: int
    validate_ns: int
    prep_ns: int
    call_ns: int


_records: collections.deque = collections.deque(maxlen=KEEP)
_ids = itertools.count()


def _no_span(name: str):
    return contextlib.nullcontext()


@contextlib.contextmanager
def call():
    """A `run()` call's root span `run`. Yields `span(name)`, which opens
    the phase `name` of this call as a context manager; the call's record
    is kept when it returns. An annotation cannot be withdrawn once opened,
    so a call traced by an outer transformation is told apart before any
    span opens, and gets spans that do nothing."""
    if not jax.core.trace_ctx.is_top_level():
        yield _no_span
        return
    cid = next(_ids)
    took = {}  # span name -> (start ns, duration ns)

    @contextlib.contextmanager
    def span(name: str):
        with jax.profiler.TraceAnnotation(name, call=cid):
            t0 = time.perf_counter_ns()
            yield
            took[name] = (t0, time.perf_counter_ns() - t0)

    with span("run"):
        yield span
    start, run_ns = took["run"]
    _records.append(CallRecord(
        cid, start, run_ns, took["run.validate"][1], took["run.prep"][1], took["run.call"][1],
    ))


def recent(k: int) -> list[CallRecord]:
    """The last `k` call records (fewer if fewer are kept), oldest first."""
    if k <= 0:
        return []
    return list(itertools.islice(_records, max(0, len(_records) - k), None))


class RowOccupancy(NamedTuple):
    """A kernel's rows, as the last program traced that calls it holds them."""

    rows: int    # rows that carry a chain, per call
    padded: int  # rows the MXU processes per call: `rows` padded to its row tile
    calls: int   # calls per step


_occupancy: dict[str, RowOccupancy] = {}


def note_rows(kernel: str, rows: int, padded: int, calls: int) -> None:
    """Note the rows of `kernel`'s calls in the program being traced."""
    _occupancy[kernel] = RowOccupancy(rows, padded, calls)


def row_occupancy(kernel: str) -> Optional[RowOccupancy]:
    """The last note of `kernel`'s rows, or None if none was traced."""
    return _occupancy.get(kernel)
