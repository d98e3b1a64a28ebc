"""The king's-lattice cell and the dense PCD cell, on the CPU at a cut size.

`king384` is cut in this file's own copy of the benchmark to a 16 x 16
lattice (n = 256), 8 chains of 20 sweeps with no samples; `sk2048.pcd` to
the n = 256 SK instance of `chipbench_tiny` and 8 chains. A sound run is
correct, the control is not, a broken timed path is not, the kernel's
least work is pinned, and the lattice kernel's calls per sweep are read
from the program's own note.
"""
from __future__ import annotations

import json
import os

import jax
import numpy as np
import pytest

import chipbench_tiny

KING = "king384.anneal"
CELLS = [KING, "sk2048.pcd"]
KING_CUT = {"H": 16, "W": 16, "n": 256}
KING_TRAFFIC = {"chains": 8, "steps": 20, "sample_every": 0, "check_jobs": 2}
KING_TARGET = -0.9


def _edit(path: str, change) -> None:
    with open(path) as f:
        data = json.load(f)
    change(data)
    with open(path, "w") as f:
        json.dump(data, f)


@pytest.fixture(scope="module")
def here(tmp_path_factory):
    here = chipbench_tiny.tree(str(tmp_path_factory.mktemp("chipbench_king")))
    _edit(os.path.join(here, "configs", "king384.json"), lambda c: c.update(KING_CUT))

    def cut(c):
        c["traffic"] = {**c.get("traffic", {}), **KING_TRAFFIC}
        c["target_energy_per_spin"] = KING_TARGET

    _edit(os.path.join(here, "cells", KING + ".json"), cut)
    _edit(os.path.join(here, "cells", "sk2048.pcd.json"),
          lambda c: c.update(traffic={**c.get("traffic", {}), **chipbench_tiny.PCD}))
    return here


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(here, workload):
    correct, readings, limits, _ = chipbench_tiny.run(here, workload)
    assert correct, (readings, limits)
    assert readings["segments_differing"] == 0
    if workload == KING:
        assert readings["hit_mismatch"] == 0


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(here, workload):
    correct, readings, limits, _ = chipbench_tiny.run(here, workload, job_kind="control")
    assert not correct, (readings, limits)


def _unchanged(monkeypatch):
    from repro.kernels import ops

    monkeypatch.setattr(ops, "lattice_gibbs_sweep", lambda s, *a, **k: s)
    monkeypatch.setattr(ops, "tau_leap_step", lambda s, *a, **k: s)


def _spin_altered(monkeypatch):
    from repro.kernels import ops

    for name in ("lattice_gibbs_sweep", "tau_leap_step"):
        kernel = getattr(ops, name)

        def altered(s, *a, _kernel=kernel, **k):
            return _kernel(s, *a, **k).at[..., 0].multiply(-1.0)

        monkeypatch.setattr(ops, name, altered)


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", [_unchanged, _spin_altered], ids=["unchanged", "spin_altered"])
def test_broken_timed_path_is_not_correct(here, monkeypatch, workload, fault):
    fault(monkeypatch)
    correct, readings, limits, _ = chipbench_tiny.run(here, workload)
    assert not correct, (readings, limits)


def test_lattice_work_pinned():
    """Hand-computed least work of one sweep of the king384 cell."""
    from chipbench import harness

    dynamics = harness.load_cell(KING).dynamics
    ops, nbytes = dynamics.work({"n": 147456}, 128)
    assert ops == 16 * 147456 * 128 == 301989888
    assert nbytes == 32 * 147456 + 2 * 128 * 147456 == 42467328


def test_instance_planes_are_symmetric_and_plus_minus_one():
    """Every king's edge carries one ±1 coupling, seen from both ends."""
    from chipbench import harness

    problem = harness.load_cell(KING).problem
    H, W = 6, 5
    w = problem.planes(H, W, 3)
    for k, (dy, dx) in enumerate(problem.OFFSETS):
        back = problem.OFFSETS.index((-dy, -dx))
        for y in range(H):
            for x in range(W):
                inside = 0 <= y + dy < H and 0 <= x + dx < W
                assert abs(w[k, y, x]) == (1.0 if inside else 0.0)
                if inside:
                    assert w[k, y, x] == w[back, y + dy, x + dx]


def test_energy_matches_the_program(here):
    """The yardstick's energy of flat states equals `LatticeIsing.energy`."""
    from chipbench import harness

    cell = harness.load_cell(KING, here=here)
    inst = harness.make_instance(cell)
    problem = cell.problem.program_problem(inst)
    s = (2 * jax.random.bernoulli(jax.random.key(1), 0.5, (4, 16, 16)) - 1).astype(np.float32)
    want = np.asarray(jax.vmap(problem.energy)(s))
    got = np.asarray(cell.problem.energy(inst, s.reshape(4, 256), "full"))
    np.testing.assert_array_equal(got, want)


def test_lattice_calls_per_step_reads_the_chains(here):
    """Under `run(n_chains=...)` the kernel is called once per chain per sweep."""
    from chipbench import harness
    from repro.core import sampler_api

    cell = harness.load_cell(KING, here=here)
    problem = cell.problem.program_problem(harness.make_instance(cell))
    jax.clear_caches()  # the note is made while the program is traced
    for chains in (3, 8):
        sampler_api.run(problem, "chromatic_gibbs", jax.random.key(0), n_steps=2,
                        n_chains=chains, backend="pallas")
        assert cell.readers["lattice_calls_per_step"].read(None) == chains
