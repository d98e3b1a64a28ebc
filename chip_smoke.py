"""Smoke run of the sampler on one TPU chip, through `sampler_api.run()`.

    python chip_smoke.py [--seed N]

Three phases run in this one process, each through the public entry point
`sampler_api.run(..., backend="pallas")` at a size users bring:

  dense    SK n=2048, tau_leap, 128 chains (fused int8 MXU step)
  lattice  128x128 king's-graph ferromagnet, chromatic_gibbs, 64 chains
  sparse   random 3-regular MaxCut n=4096, colored_gibbs, 64 chains

Each phase checks that
  1. its compiled run program holds a Pallas kernel (`tpu_custom_call`),
     so nothing ran in interpret mode;
  2. one call of its kernel agrees on the chip with the `repro.kernels.ref`
     oracle on the same inputs, to ORACLE_MAX_MISMATCH;
  3. the run's energies are finite, and every chain's energy falls from
     its random start by the phase's margin.
Any failed check exits nonzero. The seconds printed are smoke timings (one
compile, one warm call), not benchmark numbers.

There is no CPU fallback: on any platform but a TPU the script exits
nonzero before a phase runs. The last line of output is one JSON object,
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from typing import Any, Callable

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.report import use_compile_cache  # noqa: E402
from repro.core import problems, sampler_api  # noqa: E402
from repro.core.ising import king_color_masks  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402

# Largest share of sites at which a kernel call may differ from its oracle.
# The two paths round sigmoid/exp differently, so a site whose uniform lies
# within float rounding of its flip probability may resolve either way, and
# in a sweep such a site changes its neighbours' fields in later color
# phases. A wrong kernel differs at a large share of the sites.
ORACLE_MAX_MISMATCH = 1e-4


@dataclasses.dataclass(frozen=True)
class Phase:
    """One problem, kernel and chain count, run through `run()` and checked."""

    name: str
    problem: Any
    kernel: Any                # registered name or SamplerKernel
    chains: int
    steps: int
    schedule: sampler_api.Schedule
    min_fall: float            # each chain's energy must fall at least this much
    min_fall_why: str
    oracle: Callable           # (phase, key) -> (kernel output, oracle output)


def fail(msg: str) -> None:
    """Exit nonzero with the failed check on stderr."""
    sys.exit(f"chip_smoke: FAILED: {msg}")


def require_kernel(compiled, what: str) -> None:
    """The compiled program holds a Pallas TPU kernel: nothing ran in
    interpret mode."""
    if "tpu_custom_call" not in compiled.as_text():
        fail(f"{what}: no tpu_custom_call in the compiled program")


def dense_oracle(ph: Phase, key):
    """One fused tau-leap step (beta 1) at the phase's batch, as TauLeap
    calls it."""
    problem = ph.problem
    j_i8, scale = ops.quantize_dense(problem.J)
    k_s, k_u = jax.random.split(key)
    n = problem.J.shape[0]
    args = (
        sampler_api.random_init(k_s, (ph.chains, n)), j_i8, problem.b, scale,
        jax.random.uniform(k_u, (ph.chains, n)), jnp.asarray(ph.kernel.dt, jnp.float32),
    )
    return (
        _kernel_call(ops.tau_leap_step, args, "dense oracle"),
        jax.jit(ref.tau_leap_step_ref)(*args),
    )


def lattice_oracle(ph: Phase, key, beta=0.5):
    """One fused 4-color sweep of the lattice near its critical beta, as
    ChromaticGibbs calls it."""
    problem, chains = ph.problem, ph.chains
    H, W = problem.shape
    colors = king_color_masks(H, W)
    k_s, k_u = jax.random.split(key)
    s = sampler_api.random_init(k_s, (chains, H, W))
    u = jax.random.uniform(k_u, (colors.shape[0], chains, H, W))
    frozen, clamp = problem.frozen_mask, problem.frozen_values.astype(jnp.float32)
    beta = jnp.asarray(beta, jnp.float32)
    got = _kernel_call(
        ops.lattice_gibbs_sweep,
        (s, problem.w, problem.b, u, colors.astype(jnp.float32),
         frozen.astype(jnp.float32), clamp, beta),
        "lattice oracle",
    )
    want = jax.jit(ref.lattice_gibbs_sweep_ref)(
        s, problem.w, problem.b, u, colors, frozen, clamp, beta
    )
    return got, want


def sparse_oracle(ph: Phase, key, beta=1.0):
    """One fused colored sweep of the graph, as ColoredGibbs calls it."""
    problem, chains = ph.problem, ph.chains
    masks = problem.color_masks
    k_s, k_u = jax.random.split(key)
    s = sampler_api.random_init(k_s, (chains, problem.n))
    u = jax.random.uniform(k_u, (masks.shape[0], chains, problem.n))
    beta = jnp.asarray(beta, jnp.float32)
    tables = (problem.nbr_idx, problem.nbr_w, problem.b)
    got = _kernel_call(
        ops.colored_gibbs_sweep, (s, *tables, u, masks.astype(jnp.float32), beta),
        "sparse oracle",
    )
    want = jax.jit(ref.colored_gibbs_sweep_ref)(s, *tables, u, masks, beta)
    return got, want


def _kernel_call(op, args, what):
    """Compile op on its own, check it holds the kernel, run it."""
    compiled = jax.jit(op).lower(*args).compile()
    require_kernel(compiled, what)
    return compiled(*args)


def phases(seed: int) -> list[Phase]:
    """The dense, lattice and sparse phases, with problems made from seed."""
    n_dense = 2048
    sk = problems.sk_instance(n_dense, seed)
    ferro = problems.get_problem("ferromagnet", 128, seed)
    maxcut = problems.random_3regular_maxcut(4096, seed)
    n_edges = int(maxcut.deg.sum()) // 2
    return [
        Phase(
            "dense", sk, sampler_api.TauLeap(dt=0.25), 128, 300,
            sampler_api.geometric(0.1, 3.0),
            min_fall=0.5 * n_dense,
            min_fall_why="0.5 per spin; the SK ground state is about -0.76 per spin",
            oracle=dense_oracle,
        ),
        Phase(
            "lattice", ferro.problem, "chromatic_gibbs", 64, 200,
            sampler_api.geometric(0.1, 1.0),
            min_fall=0.75 * abs(ferro.ref_energy),
            min_fall_why=f"3/4 of the ground-state energy {ferro.ref_energy:.0f}",
            oracle=lattice_oracle,
        ),
        Phase(
            "sparse", maxcut, "colored_gibbs", 64, 300,
            sampler_api.geometric(0.1, 3.0),
            min_fall=0.6 * n_edges,
            min_fall_why=f"0.6 per edge of {n_edges}; a maximum cut gives about 0.8",
            oracle=sparse_oracle,
        ),
    ]


def run_phase(ph: Phase, seed: int) -> None:
    """Compile and run one phase, print its lines, and exit on a failed check."""
    tag = f"[{ph.name}]"
    k_s0, k_run, k_oracle = jax.random.split(jax.random.key(seed), 3)
    s0 = sampler_api.random_init(k_s0, (ph.chains,) + sampler_api.state_shape(ph.problem))

    def sample(problem, s0, key):
        return sampler_api.run(
            problem, ph.kernel, key, n_steps=ph.steps, s0=s0, n_chains=ph.chains,
            schedule=ph.schedule, sample_every=ph.steps // 10, backend="pallas",
        )

    t0 = time.perf_counter()
    compiled = jax.jit(sample).lower(ph.problem, s0, k_run).compile()
    compile_s = time.perf_counter() - t0
    require_kernel(compiled, f"{ph.name} run program")
    print(f"{tag} run program holds tpu_custom_call: ok", flush=True)

    t0 = time.perf_counter()
    res = jax.block_until_ready(compiled(ph.problem, s0, k_run))
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(compiled(ph.problem, s0, k_run))
    warm_s = time.perf_counter() - t0
    print(f"{tag} smoke timing, not a benchmark number: compile {compile_s:.2f} s, "
          f"first call {first_s:.3f} s, warm call {warm_s:.3f} s "
          f"({ph.chains} chains x {ph.steps} steps)", flush=True)

    got, want = ph.oracle(ph, k_oracle)
    differ = int(jnp.sum(got != want))
    allowed = int(ORACLE_MAX_MISMATCH * want.size)
    print(f"{tag} oracle check: {differ} of {want.size} sites differ from "
          f"repro.kernels.ref (allowed {allowed})", flush=True)
    if differ > allowed:
        fail(f"{ph.name}: kernel disagrees with its oracle at {differ} sites")

    e0 = ph.problem.energy(s0)
    e1 = ph.problem.energy(res.s)
    finite = bool(jnp.isfinite(res.energies).all() & jnp.isfinite(e1).all())
    fall = float(jnp.min(e0 - e1))
    print(f"{tag} energy check: start mean {float(e0.mean()):.1f}, final mean "
          f"{float(e1.mean()):.1f}, smallest fall {fall:.1f} (required "
          f"{ph.min_fall:.1f}: {ph.min_fall_why}), finite {finite}", flush=True)
    if not finite:
        fail(f"{ph.name}: non-finite energies")
    if not fall >= ph.min_fall:
        fail(f"{ph.name}: a chain's energy fell by only {fall:.1f}")


def main(argv: list[str] | None = None) -> int:
    """Check the platform, then run every phase; 0 when all checks pass."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0, help="problem and PRNG seed")
    args = ap.parse_args(argv)

    cache_dir = use_compile_cache(ROOT)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"needs a TPU, found platform {dev.platform!r} ({dev.device_kind}); "
             "there is no CPU fallback")
    print(f"device: platform={dev.platform} kind={dev.device_kind} count={len(devices)}")
    print(f"jax {jax.__version__}, compile cache {cache_dir}", flush=True)

    for ph in phases(args.seed):
        run_phase(ph, args.seed)
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
