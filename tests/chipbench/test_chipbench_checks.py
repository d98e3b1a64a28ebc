"""The comparison that decides `correct`, on the CPU at a cut size.

A sound run of each cell is correct; the control (the reference in the
precision below the configuration's, put in the program's place) is not;
and a run with the timed path broken underneath is not, for each fault
the cell can have: a step that returns its state unchanged, half of the
chains left out with the other half standing in for them, a spin altered
where the kernel produces it, and (where jobs record energies) an energy
altered where `run()` produces it. A cell on one chip has no exchange
between chips to leave out.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

import chipbench_tiny

CELLS = ["sk2048.anneal", "maxcut3r4096.anneal", "maxcut3r4096.pcd"]


@pytest.fixture(scope="module")
def here(tmp_path_factory):
    return chipbench_tiny.tree(str(tmp_path_factory.mktemp("chipbench_cut")))


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(here, workload):
    correct, readings, limits, _ = chipbench_tiny.run(here, workload)
    assert correct, (readings, limits)


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(here, workload):
    correct, readings, limits, _ = chipbench_tiny.run(here, workload, job_kind="control")
    assert not correct, (readings, limits)


def _unchanged(monkeypatch):
    from repro.kernels import ops

    monkeypatch.setattr(ops, "tau_leap_step", lambda s, *a, **k: s)
    monkeypatch.setattr(ops, "colored_gibbs_sweep", lambda s, *a, **k: s)


def _half_chains(monkeypatch):
    from repro.core import sampler_api

    full = sampler_api._run_batched

    def half(problem, kernel, keys, s0, betas, e_target, n_steps, sample_every,
             track_hit, n_chains, *rest):
        h = n_chains // 2
        r = full(problem, kernel, keys[:h], None if s0 is None else s0[:h], betas,
                 e_target, n_steps, sample_every, track_hit, h, *rest)
        return jax.tree.map(lambda x: jnp.concatenate([x, x]), r)

    monkeypatch.setattr(sampler_api, "_run_batched", half)


def _spin_altered(monkeypatch):
    from repro.kernels import ops

    for name in ("tau_leap_step", "colored_gibbs_sweep"):
        kernel = getattr(ops, name)

        def altered(s, *a, _kernel=kernel, **k):
            return _kernel(s, *a, **k).at[..., 0].multiply(-1.0)

        monkeypatch.setattr(ops, name, altered)


def _energy_altered(monkeypatch):
    from repro.core.ising import DenseIsing
    from repro.core.sparse import SparseIsing

    for cls in (DenseIsing, SparseIsing):
        energy = cls.energy
        monkeypatch.setattr(cls, "energy", lambda self, s, _e=energy: _e(self, s) + 1.0)


FAULTS = {
    "unchanged": _unchanged,
    "half_chains": _half_chains,
    "spin_altered": _spin_altered,
    "energy_altered": _energy_altered,
}


@pytest.mark.parametrize(
    "workload,fault",
    [(w, f) for w in CELLS for f in FAULTS if not (w.endswith(".pcd") and f == "energy_altered")],
)
def test_broken_timed_path_is_not_correct(here, monkeypatch, workload, fault):
    FAULTS[fault](monkeypatch)
    correct, readings, limits, _ = chipbench_tiny.run(here, workload)
    assert not correct, (readings, limits)
