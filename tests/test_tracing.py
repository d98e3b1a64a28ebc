"""`sampler_api.run()`'s host spans and per-call records (`repro.core.tracing`)."""
from __future__ import annotations

import collections
import glob
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ising, sampler_api, tracing


def _dense(n: int, seed: int = 0) -> ising.DenseIsing:
    rng = np.random.default_rng(seed)
    J = rng.normal(size=(n, n)).astype(np.float32) / np.sqrt(n)
    J = (J + J.T) / 2
    np.fill_diagonal(J, 0.0)
    return ising.DenseIsing(J=jnp.asarray(J), b=jnp.zeros(n, jnp.float32))


def _run(problem, seed=0, **kw):
    kw = {"n_steps": 4, "n_chains": 3, **kw}
    return sampler_api.run(problem, "tau_leap", jax.random.key(seed), **kw)


def _last_id():
    last = tracing.recent(1)
    return last[0].call if last else None


@pytest.mark.parametrize("n_chains", [1, 3])
def test_each_call_leaves_one_record_with_nested_phases(n_chains):
    problem = _dense(8)
    before = _last_id()
    _run(problem, n_chains=n_chains)
    _run(problem, seed=1, n_chains=n_chains)
    recs = tracing.recent(2)
    assert recs[0].call != before and recs[1].call == recs[0].call + 1
    for r in recs:
        phases = (r.validate_ns, r.prep_ns, r.call_ns)
        assert all(p > 0 for p in phases)
        assert sum(phases) <= r.run_ns
    assert recs[0].start_ns + recs[0].run_ns <= recs[1].start_ns


def test_timeit_passes_lie_in_run_call():
    """Under `timeit=True` both passes of the program lie in `run.call`."""
    res = _run(_dense(8), timeit=True)
    rec = tracing.recent(1)[0]
    assert rec.call_ns * 1e-9 >= res.timing.compile_s + res.timing.wall_s


def test_record_is_bounded(monkeypatch):
    monkeypatch.setattr(tracing, "_records", collections.deque(maxlen=3))
    problem = _dense(8)
    for seed in range(5):
        _run(problem, seed=seed)
    recs = tracing.recent(10)
    assert len(recs) == 3
    assert [r.call for r in recs] == list(range(recs[0].call, recs[0].call + 3))
    assert tracing.recent(0) == []
    assert tracing._records.maxlen == 3 and tracing.KEEP >= 8192


def test_no_record_under_jit_or_on_error():
    problem = _dense(8)
    _run(problem)
    before = _last_id()
    jax.jit(lambda k: sampler_api.run(problem, "tau_leap", k, n_steps=4).s)(jax.random.key(2))
    assert _last_id() == before
    J = np.zeros((6, 6), np.float32)
    J[0, 1] = J[1, 0] = np.inf
    bad = ising.DenseIsing(J=jnp.asarray(J), b=jnp.zeros(6))
    with pytest.raises(sampler_api.NonFiniteEnergyError):
        sampler_api.run(bad, "tau_leap", jax.random.key(0), n_steps=2)
    assert _last_id() == before


def _profile_spans(log_dir):
    """Host events named in `tracing.SPANS`, as (name, start, end, call)."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name in tracing.SPANS:
                        out.append((e.name, e.start_ns, e.end_ns, dict(e.stats).get("call")))
    return out


def test_spans_in_the_profiler_trace_nest_and_share_the_call_id(tmp_path):
    """On the CPU, too, the spans land in the host plane of the profiler's
    trace: the four of one call carry its id, the phases lie inside `run`
    in order, and a `run()` traced by `jax.jit` writes none. The results
    are the same bits as without the profiler."""
    problem = _dense(8)
    plain = _run(problem, seed=3, sample_every=2)
    jitted = jax.jit(lambda k: sampler_api.run(problem, "tau_leap", k, n_steps=4).s)
    with jax.profiler.trace(str(tmp_path)):
        traced = _run(problem, seed=3, sample_every=2)
        jax.block_until_ready(jitted(jax.random.key(4)))
    for a, b in zip(jax.tree_util.tree_leaves(plain), jax.tree_util.tree_leaves(traced)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    rec = tracing.recent(1)[0]
    spans = _profile_spans(str(tmp_path))
    assert sorted(name for name, *_ in spans) == sorted(tracing.SPANS)
    assert {c for *_, c in spans} == {rec.call}
    by = {name: (a, b) for name, a, b, _ in spans}
    lo, hi = by["run"]
    order = [by[name] for name in tracing.SPANS[1:]]
    assert all(lo <= a <= b <= hi for a, b in order)
    assert all(order[i][1] <= order[i + 1][0] for i in range(len(order) - 1))
