"""The per-layer metrics of `run()`'s host path that read the program's own
record of its calls, what each of the program's spans covers, and the trace
reduction's numbers on the chip fixture, pinned."""
from __future__ import annotations

import collections
import os
import sys
import time
import types
from unittest import mock

import jax
import pytest

import chipbench_tiny  # noqa: F401  (puts the repo and src/ on sys.path)
from chipbench import harness, run_record, trace

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "pcd_small.xplane.pb")
READERS = {"run_validate_ms": "validate_ns", "run_call_ms": "call_ns"}


def _reader(name):
    return harness.load_module(os.path.join(harness.HERE, "metrics", name + ".py"))


def _ctx(jobs):
    return types.SimpleNamespace(window=types.SimpleNamespace(jobs=jobs))


def _records(monkeypatch, n):
    """A record of `n` calls whose call `i` took i ms in each span."""
    from repro.core import tracing

    recs = collections.deque(maxlen=tracing.KEEP)
    for i in range(n):
        recs.append(tracing.CallRecord(i, 10**9 * i, *(10**6 * i,) * 4))
    monkeypatch.setattr(tracing, "_records", recs)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_is_the_mean_over_the_window_calls(monkeypatch, name):
    _records(monkeypatch, 5)  # a warm-up call, then a window of 4 jobs
    assert _reader(name).read(_ctx(4)) == pytest.approx((1 + 2 + 3 + 4) / 4)


@pytest.mark.parametrize("jobs", [0, 6])
@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads_nothing_without_every_window_call(monkeypatch, name, jobs):
    _records(monkeypatch, 5)
    assert _reader(name).read(_ctx(jobs)) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads_nothing_from_a_program_without_the_record(monkeypatch, name):
    import repro.core

    monkeypatch.delattr(repro.core, "tracing", raising=False)
    monkeypatch.setitem(sys.modules, "repro.core.tracing", None)
    assert run_record.window_calls(_ctx(3)) is None
    assert _reader(name).read(_ctx(3)) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads_the_last_kept_calls_of_a_longer_window(monkeypatch, name):
    from repro.core import tracing

    monkeypatch.setattr(tracing, "KEEP", 3)
    _records(monkeypatch, 5)  # the record keeps calls 2, 3, 4 of a window of 10
    assert _reader(name).read(_ctx(10)) == pytest.approx((2 + 3 + 4) / 3)


@pytest.fixture(scope="module")
def pcd_window(tmp_path_factory):
    """A real window of the cut pcd cell on the CPU, read as the benchmark
    reads it when the window ends, and the host clock's time of every eager
    (not traced) energy evaluation of its problem."""
    from repro.core.sparse import SparseIsing

    here = chipbench_tiny.tree(str(tmp_path_factory.mktemp("pcd")))
    cell = harness.load_cell("maxcut3r4096.pcd", here=here)
    inst = harness.make_instance(cell)
    inputs = harness.seed_inputs(cell, inst, 2**33 + 11)
    energy, eager_ns = SparseIsing.energy, []

    def timed_energy(self, s):
        if not isinstance(s, jax.core.Tracer):
            eager_ns.append(time.perf_counter_ns())
        return energy(self, s)

    with mock.patch.object(harness, "check_kernel", lambda *a: None), \
            mock.patch.object(SparseIsing, "energy", timed_energy):
        job = harness.prepare(cell, inst, inputs)
        m = harness.measure(cell, inst, job, inputs, 7, 0.3)
    ctx = harness.Context(cell, m.window, None, "cpu", inst)
    read = {name: cell.readers[name].read(ctx) for name in (*READERS, "run_dispatch_ms")}
    read.update({f: run_record.mean_span_ms(ctx, f) for f in ("run_ns", "prep_ns")})
    return types.SimpleNamespace(
        cell=cell, jobs=m.window.jobs, calls=run_record.window_calls(ctx), read=read,
        eager_ns=eager_ns,
    )


def test_cut_pcd_window_reads_every_call(pcd_window):
    """The record holds the window's calls, the warm-up job's left out."""
    w = pcd_window
    assert w.calls is not None and len(w.calls) == w.jobs > 1
    assert set(READERS) <= set(w.cell.readers)
    assert all(w.read[name] > 0 for name in READERS)


def test_cut_pcd_run_span_is_nearly_the_dispatch_span(pcd_window):
    """The phases lie inside `run`, and `run` covers nearly all of the
    harness's `dispatch` span (`run_dispatch_ms`), so host work cannot
    leave the program's spans while staying in `run()`'s call."""
    r = pcd_window.read
    phases_ms = r["run_validate_ms"] + r["prep_ns"] + r["run_call_ms"]
    assert phases_ms <= r["run_ns"] <= r["run_dispatch_ms"]
    assert r["run_ns"] >= 0.8 * r["run_dispatch_ms"]


def test_cut_pcd_probe_falls_in_run_validate(pcd_window):
    """Every eager energy evaluation made inside a `run()` call (the
    finite-energy probe) lies inside that call's `run.validate`, the first
    phase of `run`. A `run()` that makes none passes."""
    w = pcd_window
    inside = [(t, c) for t in w.eager_ns for c in w.calls if c.start_ns <= t <= c.start_ns + c.run_ns]
    assert all(t - c.start_ns <= c.validate_ns for t, c in inside)


def test_fixture_summary_is_pinned():
    """Every number the reduction gives on the committed chip trace (5 pcd
    jobs, harness spans only), as recorded."""
    s = trace.summarize(trace.load(FIXTURE))
    assert s.busy_s == pytest.approx(0.024542143, abs=1e-12)
    assert s.window_s == pytest.approx(0.103874392, abs=1e-12)
    assert s.kernel_s == pytest.approx(0.023678869, abs=1e-12)
    assert s.device_ops[:3] == [["colored_gibbs_sweep.2", pytest.approx(0.023678869, abs=1e-12)],
                                ["fusion", pytest.approx(0.000350005, abs=1e-12)],
                                ["add_maximum_fusion.1", pytest.approx(5.4401e-05, abs=1e-12)]]
    assert len(s.device_ops) == 10
    gaps = [[name, pytest.approx(v, abs=1e-12)] for name, v in [
        ["block_wait", 0.002829626], ["block_wait", 0.002578446], ["block_wait", 0.002346892],
        ["block_wait", 0.00208996], ["block_wait", 0.001979718], ["dispatch", 0.001157511],
        ["dispatch", 0.00115478], ["dispatch", 0.001094729], ["dispatch", 0.001042952],
        ["dispatch", 0.001001211]]]
    assert s.idle_gaps == gaps
