"""Paper-figure benchmarks — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run --figures [--only name] [--fast]

Prints `name,us_per_call,derived` CSV rows (derived = the figure's headline
quantity). Sampling benchmarks go through the unified driver
(`sampler_api.run`) with kernels selected by registry name. The suite-based
throughput/TTS harness lives in `benchmarks.runner`; this module keeps the
qualitative paper reproductions. Functions:

  fig3a_fidelity      — TV(sampled, exact Boltzmann) per registered kernel
  figS9_delay_skew    — tau-leap dt sweep == the chip's delay-ratio study
  fig3gh_scaling      — async vs sync TTS scaling + A e^{B sqrt n} fits
  fig3i_solver_comparison — solver zoo TTS on one MaxCut instance
  fig4d_ml_sampling   — time/sample: PASS (flat, model time) vs CPU Gibbs
  fig4e_energy        — energy/sample projection from paper power numbers
  fig5_decision       — bifurcation distance vs eta
  driver              — run() wall time per kernel + multi-chain batching
  kernels             — Pallas kernel wall time (interpret mode off a TPU)
  roofline            — dry-run roofline table from artifacts/
"""
from __future__ import annotations

import glob
import json
import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import ctmc, ising, problems, sampler_api, samplers
from repro.core.glauber import LAMBDA0_CHIP_HZ
from repro.kernels import ops

FAST = False


def _row(name, us, derived):
    print(f"{name},{us:.2f},{derived}", flush=True)


def _timeit(fn, n=5):
    fn()  # compile
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6


# ---------------------------------------------------------------------------


def fig3a_fidelity():
    """TV distance to the exact Boltzmann distribution, per registered
    kernel, all through the one sampler_api.run driver."""
    rng = np.random.default_rng(0)
    n = 6
    A = rng.normal(0, 0.6, (n, n))
    J = np.triu(A, 1)
    J = J + J.T
    prob = ising.DenseIsing(J=jnp.asarray(J, jnp.float32), b=jnp.asarray(rng.normal(0, 0.3, n), jnp.float32))
    _, p_exact = ising.enumerate_boltzmann(prob)
    s0 = samplers.random_init(jax.random.key(1), (n,))
    steps = 40_000 if FAST else 150_000

    def tv(emp):
        """Total-variation distance between two distributions."""
        return 0.5 * float(np.abs(np.asarray(emp) - p_exact).sum())

    runs = [
        ("sync_gibbs", "random_scan_gibbs", 2, dict(n_steps=steps, sample_every=2)),
        ("async_ctmc", "ctmc", 3, dict(n_steps=steps // 3, sample_every=1)),
        (
            "tau_leap(dt=0.05)",
            sampler_api.TauLeap(dt=0.05),
            4,
            dict(n_steps=steps, sample_every=2),
        ),
    ]
    for label, kernel, seed, kw in runs:
        t0 = time.perf_counter()
        res = sampler_api.run(prob, kernel, jax.random.key(seed), s0=s0, **kw)
        if label == "async_ctmc":
            emp = ctmc.time_weighted_distribution(ctmc.CTMCRun.from_result(res), n)
        else:
            emp = ctmc.empirical_distribution(res.samples.reshape(-1, n), n)
        _row(f"fig3a_fidelity/{label}", (time.perf_counter() - t0) * 1e6, f"tv={tv(emp):.4f}")


def figS9_delay_skew():
    """tau-leap dt sweep: distribution skew vs dt — the TPU analogue of the
    chip's circuit-delay-ratio study (Fig. S9)."""
    rng = np.random.default_rng(1)
    n = 6
    A = rng.normal(0, 0.8, (n, n))
    J = np.triu(A, 1)
    J = J + J.T
    prob = ising.DenseIsing(J=jnp.asarray(J, jnp.float32), b=jnp.zeros((n,), jnp.float32))
    _, p_exact = ising.enumerate_boltzmann(prob)
    s0 = samplers.random_init(jax.random.key(1), (n,))
    for dt in (1.6, 0.8, 0.4, 0.2, 0.1, 0.05):
        steps = int((30_000 if FAST else 100_000) * min(1.0, 0.4 / dt) + 20_000)
        t0 = time.perf_counter()
        run = sampler_api.run(
            prob, sampler_api.TauLeap(dt=dt), jax.random.key(5),
            n_steps=steps, s0=s0, sample_every=2,
        )
        emp = ctmc.empirical_distribution(run.samples.reshape(-1, n), n)
        tv = 0.5 * float(np.abs(np.asarray(emp) - p_exact).sum())
        _row(f"figS9_delay_skew/dt={dt}", (time.perf_counter() - t0) * 1e6, f"tv={tv:.4f}")


def fig3gh_scaling():
    """Async vs sync time-to-solution scaling on MaxCut and SK (Fig 3G/H,
    Table S1), run through the shared `benchmarks.scaling` harness — the
    same size-sweep/fit/p-value machinery the suite records embed — with
    the CTMC as the async exemplar. Model time at equal per-neuron update
    rate lambda0=1; targets come from the zoo's reference energies instead
    of this figure's former private long-reference-run loop."""
    from benchmarks import scaling as scaling_mod

    sizes = (10, 20, 30, 45, 60, 80) if not FAST else (10, 20, 30)
    for problem_kind in ("maxcut", "sk"):
        spec = scaling_mod.ScalingSpec(
            problem=problem_kind,
            sizes=sizes,
            n_instances=5 if not FAST else 3,
            n_trials=24 if not FAST else 8,
            steps_base=4000,
            steps_per_n=80,
            n_boot=300,
        )
        t0 = time.perf_counter()
        rec = scaling_mod.run_scaling(spec, log=lambda m: None)
        wall = (time.perf_counter() - t0) * 1e6
        sync = rec["kernels"][rec["sync_kernel"]]
        async_ = rec["kernels"]["ctmc"]
        gap = rec["gap_vs_sync"]["ctmc"]
        if sync["tts_median"][-1] and async_["tts_median"][-1]:
            ratio = f"{sync['tts_median'][-1] / async_['tts_median'][-1]:.0f}x"
        else:
            ratio = "n/a"
        fa, fs = async_["fit"], sync["fit"]
        fmt = lambda f: (
            f"{f['B']:.3f}[{f['B_ci'][0]:.3f},{f['B_ci'][1]:.3f}]" if f else "n/a"
        )
        pval = "n/a" if gap["pvalue"] is None else f"{gap['pvalue']:.4f}"
        _row(
            f"fig3gh_scaling/{problem_kind}",
            wall,
            f"speedup@n={sizes[-1]}:{ratio};B_async={fmt(fa)};"
            f"B_sync={fmt(fs)};p_same_B={pval}",
        )


def _random_lattice(side):
    rng = np.random.default_rng(side)
    pairs = {}
    from repro.core.ising import KING_OFFSETS

    for y in range(side):
        for x in range(side):
            for dy, dx in KING_OFFSETS[4:]:
                yy, xx = y + dy, x + dx
                if 0 <= yy < side and 0 <= xx < side:
                    pairs[((y, x), (yy, xx))] = float(rng.normal() * 0.4)
    return ising.lattice_from_pairs(side, side, pairs)


def fig4d_ml_sampling():
    """Time per Boltzmann-machine sample: PASS model time (flat in lattice
    size — all neurons update in parallel) vs CPU chromatic Gibbs wall time
    (grows with n). The paper reports 180x at the 16x16 core."""
    sweeps_per_sample = 8
    for side in (8, 16, 24, 32):
        lat = _random_lattice(side)
        s0 = samplers.random_init(jax.random.key(0), (side, side))

        fn = jax.jit(
            lambda key: samplers.chromatic_gibbs(lat, key, s0, n_sweeps=sweeps_per_sample).s
        )
        us_cpu = _timeit(lambda: jax.block_until_ready(fn(jax.random.key(1))), n=10)
        # PASS async model time per sample: sweeps/sample / lambda0 — flat in n
        us_pass = sweeps_per_sample / LAMBDA0_CHIP_HZ * 1e6
        _row(
            f"fig4d_ml_sampling/side={side}",
            us_cpu,
            f"cpu_us_per_sample={us_cpu:.1f};pass_model_us_per_sample={us_pass:.3f};ratio={us_cpu/us_pass:.0f}x",
        )


def fig4e_energy():
    """Energy-to-solution projection: chip power (Table S4, 56.8 mW full
    chip at speed 7) x model time vs CPU power x wall time (paper: 7 W
    single core)."""
    sweeps = 8
    side = 16
    lat = _random_lattice(side)
    s0 = samplers.random_init(jax.random.key(0), (side, side))
    fn = jax.jit(lambda key: samplers.chromatic_gibbs(lat, key, s0, n_sweeps=sweeps).s)
    us_cpu = _timeit(lambda: jax.block_until_ready(fn(jax.random.key(1))), n=10)
    e_cpu = 7.0 * us_cpu * 1e-6  # J per sample (7 W x wall)
    us_pass = sweeps / LAMBDA0_CHIP_HZ * 1e6
    e_pass = 56.8e-3 * us_pass * 1e-6  # J per sample (56.8 mW x model time)
    _row(
        "fig4e_energy",
        us_cpu,
        f"cpu_J={e_cpu:.2e};pass_J={e_pass:.2e};energy_ratio={e_cpu/e_pass:.0f}x (paper: 23400x)",
    )


def fig3i_solver_comparison():
    """Fig 3I analogue: solver zoo on one 60-node MaxCut instance — median
    sweeps-to-best-known for PASS async, annealed-PASS, replica exchange,
    and the serial Gibbs baseline (model-time basis, lambda0=1)."""
    from repro.core import tempering

    prob = problems.random_maxcut(60, seed=11)
    s0s = jax.vmap(lambda k: samplers.random_init(k, (prob.n,)))(
        jax.random.split(jax.random.key(0), 12)
    )
    ref = samplers.gibbs_random_scan(prob, jax.random.key(9), s0s[0], n_steps=60_000, sample_every=25)
    e_star = float(jnp.min(ref.energies))

    def report(name, fn):
        """Emit one CSV row for a finished optimization pass."""
        t0 = time.perf_counter()
        hits = fn()
        us = (time.perf_counter() - t0) * 1e6
        med = np.median([h for h in hits if np.isfinite(h)]) if np.any(np.isfinite(hits)) else float("inf")
        rate = float(np.mean(np.isfinite(hits)))
        _row(f"fig3i_solvers/{name}", us, f"median_model_time={med:.1f};hit_rate={rate:.2f}")

    max_ev = 9000

    def async_pass():
        """Event-driven CTMC first-hit pass (the async solver)."""
        t, h = jax.vmap(lambda k, s: ctmc.gillespie_first_hit(prob, k, s, e_star, n_events=max_ev))(
            jax.random.split(jax.random.key(1), 12), s0s
        )
        return np.where(np.asarray(h), np.asarray(t), np.inf)

    def sync_gibbs():
        """Random-scan Gibbs baseline at fixed beta."""
        t, h = jax.vmap(lambda k, s: samplers.gibbs_first_hit(prob, k, s, e_star, n_steps=max_ev))(
            jax.random.split(jax.random.key(2), 12), s0s
        )
        return np.where(np.asarray(h), np.asarray(t), np.inf)

    def annealed():
        """Annealed tau-leap pass (linear beta ramp)."""
        n_steps = 600
        res = sampler_api.run(
            prob, sampler_api.TauLeap(dt=0.25), jax.random.key(100),
            n_steps=n_steps, s0=s0s, n_chains=12,
            schedule=sampler_api.linear(0.3, 2.5),
        )
        e = np.asarray(jax.vmap(prob.energy)(res.s))
        return np.where(e <= e_star + 1e-6, n_steps * 0.25, np.inf)

    def replica_exchange():
        """Replica-exchange pass over the same instance."""
        outs = []
        for i in range(6):
            st = tempering.init(prob, jax.random.key(200 + i), jnp.asarray([0.3, 0.6, 1.0, 1.8]))
            st, trace = tempering.run(prob, jax.random.key(300 + i), st, n_rounds=80, steps_per_round=8)
            hit = np.where(np.asarray(trace) <= e_star + 1e-6)[0]
            outs.append((hit[0] + 1) * 8 * 0.25 if len(hit) else np.inf)
        return np.asarray(outs)

    report("pass_async_ctmc", async_pass)
    report("serial_gibbs", sync_gibbs)
    report("annealed_pass", annealed)
    report("replica_exchange_pass", replica_exchange)


def driver():
    """Unified-driver wall time: every registered kernel on a common dense
    problem, plus the multi-chain batching and Pallas-dispatch paths."""
    prob = problems.sk_instance(64, seed=0)
    lat = _random_lattice(16)
    n_steps = 256 if FAST else 1024

    for name in sampler_api.kernel_names():
        dense = name in ("random_scan_gibbs", "ctmc", "tau_leap")
        p = prob if dense else lat
        steps = n_steps if name != "chromatic_gibbs" else n_steps // 4
        fn = lambda key: sampler_api.run(p, name, key, n_steps=steps).s
        us = _timeit(lambda: jax.block_until_ready(fn(jax.random.key(1))), n=5)
        _row(f"driver/{name}", us, f"us_per_step={us/steps:.3f}")

    for n_chains in (8, 64):
        fn = lambda key: sampler_api.run(
            prob, sampler_api.TauLeap(dt=0.25), key,
            n_steps=n_steps, n_chains=n_chains,
            schedule=sampler_api.geometric(0.3, 2.0),
        ).s
        us = _timeit(lambda: jax.block_until_ready(fn(jax.random.key(2))), n=5)
        _row(
            f"driver/tau_leap_chains={n_chains}",
            us,
            f"us_per_chain_step={us/(n_steps*n_chains):.4f}",
        )

    # Pallas dispatch (interpret mode off-TPU: correctness path, not speed)
    steps_p = 32
    fn = lambda key: sampler_api.run(
        prob, sampler_api.TauLeap(dt=0.25), key, n_steps=steps_p, backend="pallas"
    ).s
    us = _timeit(lambda: jax.block_until_ready(fn(jax.random.key(3))), n=2)
    # the label comes from the same platform check that picks interpret mode
    mode = "compiled" if ops.on_tpu() else "interpret"
    _row("driver/tau_leap_pallas", us, f"us_per_step={us/steps_p:.2f};mode={mode}")


def fig5_decision():
    """Bifurcation distance grows with eta (Fig 5 B-E)."""
    from repro.core import decision

    targets = np.array([[-300.0, 1000.0], [300.0, 1000.0]], np.float32)
    n_runs = 4 if FAST else 8
    for eta in (0.5, 1.0, 2.0, 4.0):
        cfg = decision.DecisionConfig(n_neurons=40, eta=eta, max_steps=150)
        t0 = time.perf_counter()
        ds = []
        for seed in range(n_runs):
            traj = decision.simulate(jax.random.key(seed), targets, cfg)
            ds.append(float(decision.bifurcation_distance(traj.positions, targets)))
        _row(
            f"fig5_decision/eta={eta}",
            (time.perf_counter() - t0) / n_runs * 1e6,
            f"median_commit_dist={np.median(ds):.0f}",
        )


def kernels():
    """Kernel wall time through `ops`: compiled on a TPU; elsewhere in
    interpret mode, a correctness path whose time is not the kernel's."""
    from repro.core.ising import king_color_masks

    B, H, W = 256, 16, 16
    ks = jax.random.split(jax.random.key(0), 7)
    s = (2 * jax.random.bernoulli(ks[0], 0.5, (B, H, W)) - 1).astype(jnp.float32)
    w8 = jax.random.normal(ks[1], (8, H, W)) * 0.4
    b = jax.random.normal(ks[2], (H, W)) * 0.2
    u = jax.random.uniform(ks[3], (4, B, H, W))
    colors = king_color_masks(H, W).astype(jnp.float32)
    frozen = jnp.zeros((H, W))
    clampv = -jnp.ones((H, W))
    fn = jax.jit(lambda s, u: ops.lattice_gibbs_sweep(s, w8, b, u, colors, frozen, clampv))
    us = _timeit(lambda: jax.block_until_ready(fn(s, u)), n=20)
    flops = B * H * W * 8 * 2 * 4  # stencil MACs x 4 colors
    _row("kernels/lattice_gibbs_sweep(B=256)", us, f"GFLOP/s={flops/us/1e3:.2f}")

    N = 512
    s2 = (2 * jax.random.bernoulli(ks[4], 0.5, (B, N)) - 1).astype(jnp.int8)
    J8 = jax.random.randint(ks[5], (N, N), -127, 128, jnp.int32).astype(jnp.int8)
    bias = jnp.zeros((N,))
    scale = jnp.asarray(1 / 127, jnp.float32)
    u2 = jax.random.uniform(ks[6], (B, N))
    sf = s2.astype(jnp.float32)
    dt = jnp.asarray(0.25, jnp.float32)
    fn3 = jax.jit(lambda s, u: ops.tau_leap_step(s, J8, bias, scale, u, dt))
    us3 = _timeit(lambda: jax.block_until_ready(fn3(sf, u2)), n=20)
    _row("kernels/tau_leap_step(512)", us3, f"GMAC/s={B*N*N/us3/1e3:.2f}")


def roofline():
    """Summarize the dry-run artifacts (EXPERIMENTS.md builds on this)."""
    art = os.path.join(os.path.dirname(__file__), "..", "artifacts", "dryrun")
    files = sorted(glob.glob(os.path.join(art, "*__single.json")))
    if not files:
        _row("roofline", 0.0, "no dry-run artifacts; run repro.launch.dryrun first")
        return
    for f in files:
        r = json.load(open(f))
        base = os.path.basename(f).replace(".json", "")
        name = f"roofline/{base}"
        if r["status"] != "ok":
            _row(name, 0.0, r["status"])
            continue
        rf = r["roofline"]
        dom = rf["bottleneck"]
        tstep = max(rf["t_compute"], rf["t_memory"], rf["t_collective"])
        frac = rf["t_compute"] / tstep if tstep else 0.0
        _row(
            name,
            r["compile_s"] * 1e6,
            f"bottleneck={dom};t_c={rf['t_compute']:.3e};t_m={rf['t_memory']:.3e};"
            f"t_x={rf['t_collective']:.3e};roofline_frac={frac:.2f};useful={rf['useful_ratio']:.2f}",
        )


ALL = [
    fig3a_fidelity,
    figS9_delay_skew,
    fig3gh_scaling,
    fig3i_solver_comparison,
    fig4d_ml_sampling,
    fig4e_energy,
    fig5_decision,
    driver,
    kernels,
    roofline,
]


def run_figures(only: str | None = None, fast: bool = False) -> None:
    """Run the figure benchmarks (all, or those whose name contains `only`)."""
    global FAST
    FAST = fast
    print("name,us_per_call,derived")
    for fn in ALL:
        if only and only not in fn.__name__:
            continue
        fn()
