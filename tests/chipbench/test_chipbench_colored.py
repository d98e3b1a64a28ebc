"""The sparse colored Gibbs kernel's calls per sweep, read by the MaxCut
cells' `colored_calls_per_step` from the program's own note, on the CPU at
the cut size of `chipbench_tiny`."""
from __future__ import annotations

import jax
import pytest

import chipbench_tiny

CELLS = ["maxcut3r4096.anneal", "maxcut3r4096.pcd"]


@pytest.fixture(scope="module")
def here(tmp_path_factory):
    return chipbench_tiny.tree(str(tmp_path_factory.mktemp("chipbench_colored")))


@pytest.mark.parametrize("workload", CELLS)
def test_colored_calls_per_step_reads_one_call(here, workload):
    """Under `run(n_chains=...)` the chains share one kernel call per sweep."""
    from chipbench import harness
    from repro.core import sampler_api

    cell = harness.load_cell(workload, here=here)
    problem = cell.problem.program_problem(harness.make_instance(cell))
    jax.clear_caches()  # the note is made while the program is traced
    for chains in (3, 8):
        sampler_api.run(problem, "colored_gibbs", jax.random.key(0), n_steps=2,
                        n_chains=chains, backend="pallas")
        assert cell.readers["colored_calls_per_step"].read(None) == 1
