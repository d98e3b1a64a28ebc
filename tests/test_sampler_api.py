"""The unified sampler API: kernel registry, the run() driver (schedules,
striding, multi-chain batching, first-hit), and Pallas backend dispatch."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import ctmc, ising, problems, sampler_api, samplers
from repro.core.sampler_api import (
    CTMC,
    ChromaticGibbs,
    RandomScanGibbs,
    TauLeap,
    constant,
    geometric,
    linear,
    resolve_schedule,
    run,
)


def _dense_problem(n=12, seed=0, scale=0.6):
    rng = np.random.default_rng(seed)
    A = rng.normal(0, scale, (n, n))
    J = np.triu(A, 1)
    J = J + J.T
    b = rng.normal(0, scale / 2, n)
    return ising.DenseIsing(J=jnp.asarray(J, jnp.float32), b=jnp.asarray(b, jnp.float32))


def _grid_exact_problem(n=48, seed=0, denom=127):
    """Dense problem whose J sits exactly on the int8 grid, so the Pallas
    path's quantization is lossless and ref/pallas are comparable.

    `denom=128` puts J and b on multiples of 1/128 as well: every energy
    is then a sum of exact terms, the same whatever order it is added in."""
    rng = np.random.default_rng(seed)
    codes = rng.integers(-126, 127, (n, n))
    codes = np.triu(codes, 1)
    codes = codes + codes.T
    codes[0, 1] = codes[1, 0] = 127  # pin max-abs: quantize round-trips exactly
    J = jnp.asarray(codes / denom, jnp.float32)
    b = rng.normal(0, 0.2, n)
    if denom == 128:
        b = np.round(b * 128) / 128
    return ising.DenseIsing(J=J, b=jnp.asarray(b, jnp.float32))


def test_registry_has_all_kernels():
    names = sampler_api.kernel_names()
    for want in ("random_scan_gibbs", "chromatic_gibbs", "tau_leap", "ctmc"):
        assert want in names, names
    assert isinstance(sampler_api.get_kernel("tau_leap", dt=0.5), TauLeap)
    with pytest.raises(KeyError):
        sampler_api.get_kernel("metropolis_lights_out")


@pytest.mark.parametrize("name", ["random_scan_gibbs", "tau_leap", "ctmc"])
def test_dense_kernels_run_through_driver(name):
    prob = _dense_problem()
    res = run(prob, name, jax.random.key(0), n_steps=64, sample_every=8)
    assert res.s.shape == (prob.n,)
    assert res.samples.shape == (8, prob.n)
    assert res.times.shape == (8,)
    assert res.energies.shape == (8,)
    assert set(np.unique(res.samples)).issubset({-1.0, 1.0})
    assert float(res.t) > 0.0
    # recorded model times are nondecreasing and end at/below the final time
    t = np.asarray(res.times)
    assert np.all(np.diff(t) >= 0) and t[-1] <= float(res.t) + 1e-6


@pytest.mark.parametrize("name", ["chromatic_gibbs", "tau_leap"])
def test_lattice_kernels_run_through_driver(name):
    lat = problems.cal_problem(coupling=0.5)
    res = run(lat, name, jax.random.key(0), n_steps=20, sample_every=5)
    assert res.s.shape == lat.shape
    assert res.samples.shape == (4,) + lat.shape
    # clamp/dead masks respected at every observation
    frozen = np.asarray(lat.frozen_mask)
    if frozen.any():
        want = np.asarray(lat.apply_clamps(res.s))[frozen]
        np.testing.assert_array_equal(np.asarray(res.s)[frozen], want)


def test_pallas_backend_matches_ref_dense_tau_leap():
    """Acceptance: backend='pallas' (interpret mode on CPU) must match
    backend='ref' for dense tau-leap. On a grid-exact problem the int8
    field matmul is exact, so the two trajectories agree everywhere except
    (measure-zero) uniforms within float-rounding of a flip threshold."""
    prob = _grid_exact_problem()
    s0 = sampler_api.random_init(jax.random.key(1), (prob.n,))
    kw = dict(n_steps=200, s0=s0, sample_every=10)
    r_ref = run(prob, TauLeap(dt=0.25), jax.random.key(2), backend="ref", **kw)
    r_pal = run(prob, TauLeap(dt=0.25), jax.random.key(2), backend="pallas", **kw)
    assert float(np.mean(np.asarray(r_ref.s) == np.asarray(r_pal.s))) > 0.99
    assert float(np.mean(np.asarray(r_ref.samples) == np.asarray(r_pal.samples))) > 0.99
    np.testing.assert_allclose(
        np.asarray(r_ref.energies), np.asarray(r_pal.energies), rtol=1e-3, atol=1e-2
    )


@pytest.mark.parametrize("C", [3, 128, 130])
@pytest.mark.parametrize("variant", ["shared_schedule", "per_chain_schedule", "faults"])
def test_pallas_chains_as_rows_match_single_chain_runs(variant, C):
    """Under `n_chains=C` the dense tau-leap kernel runs the chains as the
    rows of one call (130 spans two row blocks). Each chain must equal, bit
    for bit, the `n_chains=1` run on its chain key: the per-row scale
    (per-chain schedule) and per-row bias (per-chain schedule, field noise)
    take the same float operations as the shared ones.

    The instance sits on multiples of 1/128 so that its energies are exact:
    run()'s energy is a batched matvec under `n_chains > 1` and XLA
    may add a batched matvec in another order than a single one."""
    prob = _grid_exact_problem(denom=128)
    n_steps = 12
    key = jax.random.key(7)
    faults = None
    if variant == "per_chain_schedule":
        schedule = np.asarray(
            jnp.linspace(0.2, 2.5, C)[:, None] * jnp.linspace(1.0, 1.6, n_steps)[None, :]
        )
    else:
        schedule = sampler_api.geometric(0.3, 3.0)
    if variant == "faults":
        stuck = np.zeros(prob.n, bool)
        stuck[[2, 11, 30]] = True
        faults = sampler_api.FaultModel(
            stuck_mask=jnp.asarray(stuck),
            stuck_values=jnp.asarray(np.where(np.arange(prob.n) % 2, 1.0, -1.0), jnp.float32),
            field_noise_std=0.7,
        )
    kw = dict(n_steps=n_steps, sample_every=4, first_hit=-60.0, backend="pallas", faults=faults)
    batched = run(prob, TauLeap(dt=0.25), key, n_chains=C, schedule=schedule, **kw)
    # passlint: ignore[PASS001] run() splits the same key into its chain keys; the split redoes it
    for c, k in enumerate(jax.random.split(key, C)):
        sched_c = schedule[c] if variant == "per_chain_schedule" else schedule
        one = run(prob, TauLeap(dt=0.25), k, schedule=sched_c, **kw)
        for field in ("s", "samples", "energies", "t_hit"):
            np.testing.assert_array_equal(
                np.asarray(getattr(batched, field))[c], np.asarray(getattr(one, field)),
                err_msg=f"chain {c}: {field}",
            )


def _masked_lattice(H=10, W=10, seed=0):
    """Small lattice with random couplings plus clamp AND dead masks, to
    exercise every branch of the fused sweep's freeze/clamp epilogue."""
    rng = np.random.default_rng(seed)
    pairs = {}
    for y in range(H):
        for x in range(W):
            for dy, dx in ising.KING_OFFSETS[4:]:
                yy, xx = y + dy, x + dx
                if 0 <= yy < H and 0 <= xx < W:
                    pairs[((y, x), (yy, xx))] = float(rng.normal(0, 0.5))
    clamp = rng.random((H, W)) < 0.1
    dead = rng.random((H, W)) < 0.05
    clampv = 2.0 * (rng.random((H, W)) < 0.5) - 1.0
    return ising.lattice_from_pairs(
        H, W, pairs, biases=rng.normal(0, 0.2, (H, W)),
        clamp_mask=clamp, clamp_value=clampv, dead_mask=dead,
    )


def test_chromatic_pallas_executes_lattice_gibbs_sweep(monkeypatch):
    """Acceptance: backend='pallas' on chromatic_gibbs must actually execute
    ops.lattice_gibbs_sweep — the dispatch used to silently no-op to ref."""
    from repro.kernels import ops

    calls = []
    orig = ops.lattice_gibbs_sweep

    def spy(*args, **kw):
        calls.append(kw.get("mode"))
        return orig(*args, **kw)

    monkeypatch.setattr(ops, "lattice_gibbs_sweep", spy)
    # n_steps=11 is used by no other test: the driver's jit cache cannot
    # already hold this signature, so tracing (and the spy) must run.
    lat = _masked_lattice()
    run(lat, ChromaticGibbs(), jax.random.key(0), n_steps=11, backend="pallas")
    assert calls and all(m == "kernel" for m in calls)
    calls.clear()
    run(lat, ChromaticGibbs(), jax.random.key(0), n_steps=11, backend="ref")
    assert calls == []


def test_chromatic_pallas_bit_parity_across_betas():
    """Acceptance: chromatic_gibbs backend='pallas' (interpret off-TPU)
    matches backend='ref' bit-for-bit at every scheduled beta, with clamp
    and dead masks active."""
    lat = _masked_lattice()
    s0 = sampler_api.random_init(jax.random.key(1), lat.shape)
    betas = jnp.tile(jnp.asarray([0.3, 1.0, 3.0], jnp.float32), 4)
    kw = dict(n_steps=12, s0=s0, sample_every=3, schedule=betas)
    r_ref = run(lat, ChromaticGibbs(), jax.random.key(2), backend="ref", **kw)
    r_pal = run(lat, ChromaticGibbs(), jax.random.key(2), backend="pallas", **kw)
    np.testing.assert_array_equal(np.asarray(r_ref.s), np.asarray(r_pal.s))
    np.testing.assert_array_equal(np.asarray(r_ref.samples), np.asarray(r_pal.samples))
    # multi-chain: the pallas step must also survive the driver's vmap
    r_mc_ref = run(lat, ChromaticGibbs(), jax.random.key(3), n_steps=6,
                   n_chains=3, sample_every=2, backend="ref")
    r_mc_pal = run(lat, ChromaticGibbs(), jax.random.key(3), n_steps=6,
                   n_chains=3, sample_every=2, backend="pallas")
    np.testing.assert_array_equal(
        np.asarray(r_mc_ref.samples), np.asarray(r_mc_pal.samples)
    )


def test_chromatic_pallas_statistical_parity_ferromagnet():
    """Acceptance: full-run() statistical parity of ref vs pallas on an 8x8
    ferromagnet — different keys, same distribution."""
    zoo = problems.get_problem("ferromagnet", 8, 0)
    kw = dict(n_steps=200, sample_every=5, n_chains=4, schedule=0.4)
    r_ref = run(zoo.problem, ChromaticGibbs(), jax.random.key(10), backend="ref", **kw)
    r_pal = run(zoo.problem, ChromaticGibbs(), jax.random.key(11), backend="pallas", **kw)
    e_ref = np.asarray(r_ref.energies)[:, 10:]  # burn-in
    e_pal = np.asarray(r_pal.energies)[:, 10:]
    se = np.hypot(e_ref.std() / np.sqrt(e_ref.size), e_pal.std() / np.sqrt(e_pal.size))
    assert abs(e_ref.mean() - e_pal.mean()) < 6 * se + 1e-6


def test_unsupported_backend_requests_raise():
    """Acceptance: requesting backend='pallas' on a kernel (or kernel/problem
    combination) without Pallas support raises — no silent ref fallback."""
    dense = _dense_problem(n=8, seed=0)
    lat = problems.cal_problem(coupling=0.5)
    for name in ("ctmc", "random_scan_gibbs"):
        with pytest.raises(ValueError, match=name):
            run(dense, name, jax.random.key(0), n_steps=4, backend="pallas")
    # tau-leap has a Pallas kernel for dense problems only
    with pytest.raises(ValueError, match="tau_leap"):
        run(lat, TauLeap(dt=0.2), jax.random.key(0), n_steps=4, backend="pallas")
    # ... and constructing the kernel with backend='pallas' directly (no
    # driver override) still refuses to silently run the stencil ref path
    with pytest.raises(NotImplementedError, match="dense problems only"):
        run(lat, TauLeap(dt=0.2, backend="pallas"), jax.random.key(0), n_steps=4)
    # trims are a ref-only feature: dispatch refuses pallas outright ...
    trim = sampler_api.glauber.SigmoidTrim(a=jnp.ones(()), b=jnp.zeros(()))
    with pytest.raises(ValueError, match="chromatic_gibbs"):
        run(lat, ChromaticGibbs(trim=trim), jax.random.key(0), n_steps=4, backend="pallas")
    # ... init() backstops direct construction without a driver override ...
    with pytest.raises(NotImplementedError, match="trim"):
        run(lat, ChromaticGibbs(trim=trim, backend="pallas"), jax.random.key(0), n_steps=4)
    # ... and 'auto' degrades to ref instead of raising (trimmed kernels
    # would otherwise break on TPU, where auto prefers pallas)
    res_trim = run(lat, ChromaticGibbs(trim=trim), jax.random.key(1), n_steps=4, backend="auto")
    assert res_trim.s.shape == lat.shape
    # 'auto' remains usable for ref-only kernels: resolves to ref off-TPU
    res = run(dense, "ctmc", jax.random.key(1), n_steps=8, backend="auto")
    assert res.s.shape == (dense.n,)


def test_problem_kind_dispatch_fails_loudly():
    """Acceptance: a kernel handed a problem kind it does not implement
    raises a ValueError naming the kernel and the kinds it supports — no
    silent densification, no shape error deep in a jitted step."""
    sp = problems.random_3regular_maxcut(8, seed=0)
    lat = problems.cal_problem(coupling=0.5)
    dense = _dense_problem(n=8)
    # lattice-only chromatic gibbs rejects sparse graphs (colored_gibbs is
    # the generalization) and dense matrices
    with pytest.raises(ValueError, match=r"chromatic_gibbs.*'sparse'"):
        run(sp, "chromatic_gibbs", jax.random.key(0), n_steps=2)
    with pytest.raises(ValueError, match=r"chromatic_gibbs.*'dense'"):
        run(dense, "chromatic_gibbs", jax.random.key(0), n_steps=2)
    # sparse-only colored gibbs rejects the rest
    with pytest.raises(ValueError, match=r"colored_gibbs.*'dense'"):
        run(dense, "colored_gibbs", jax.random.key(0), n_steps=2)
    with pytest.raises(ValueError, match=r"colored_gibbs.*'lattice'"):
        run(lat, "colored_gibbs", jax.random.key(0), n_steps=2)
    # flat-state kernels reject lattices
    for name in ("ctmc", "random_scan_gibbs"):
        with pytest.raises(ValueError, match=rf"{name}.*'lattice'"):
            run(lat, name, jax.random.key(0), n_steps=2)
    # the message names the supported kinds so the fix is obvious
    with pytest.raises(ValueError, match=r"supported problem kinds"):
        run(sp, "chromatic_gibbs", jax.random.key(0), n_steps=2)
    # sparse tau-leap exists on ref only: the driver refuses pallas ...
    with pytest.raises(ValueError, match="tau_leap"):
        run(sp, TauLeap(dt=0.2), jax.random.key(0), n_steps=2, backend="pallas")
    # ... and direct construction points at the fused sparse alternative
    with pytest.raises(NotImplementedError, match="colored_gibbs"):
        run(sp, TauLeap(dt=0.2, backend="pallas"), jax.random.key(0), n_steps=2)
    # supported sparse paths still run
    for kern in ("ctmc", "random_scan_gibbs", "tau_leap", "colored_gibbs"):
        res = run(sp, kern, jax.random.key(1), n_steps=4)
        assert res.s.shape == (sp.n,)


# beta=12: sum(rates) ~ 2e-36 — subnormal but NONZERO, the window where a
# floor-dominated categorical used to flip a near-uniform site anyway.
# beta=500: rates underflow to exactly 0 (the dt=inf -> NaN case).
# Both site-draw paths must honor the same RATE_FLOOR dwell/suppression
# semantics: the tree's zero-total descent degenerates to an arbitrary
# leaf, which `alive` must then discard exactly like the scan path.
@pytest.mark.parametrize("site_draw", ["scan", "tree"])
@pytest.mark.parametrize("beta", [12.0, 500.0])
def test_ctmc_frozen_cold_chain_stays_finite(beta, site_draw):
    """Regression: at large beta the total flip rate underflows; the dwell
    time must stay finite (clamped denominator) and NO site may flip — not
    dt=inf -> NaN time, and not a spurious flip/flip-back oscillation."""
    n = 8
    J = -0.5 * (np.ones((n, n)) - np.eye(n))
    prob = ising.DenseIsing(J=jnp.asarray(J, jnp.float32), b=jnp.zeros(n, jnp.float32))
    s0 = jnp.ones((n,), jnp.float32)  # exact ground state
    # odd n_steps + sample_every=1: a spurious flip/flip-back oscillation
    # would be caught both at the final state and at every recorded sample
    res = run(prob, CTMC(site_draw=site_draw), jax.random.key(0), n_steps=21,
              s0=s0, schedule=beta, sample_every=1)
    assert np.isfinite(float(res.t))
    assert np.all(np.isfinite(np.asarray(res.energies)))
    assert np.all(np.isfinite(np.asarray(res.times)))
    # the chain is frozen: no event may flip anything, at any step
    np.testing.assert_array_equal(np.asarray(res.s), np.asarray(s0))
    np.testing.assert_array_equal(
        np.asarray(res.samples), np.broadcast_to(np.asarray(s0), (21, n))
    )
    e0 = float(prob.energy(s0))
    np.testing.assert_array_equal(np.asarray(res.energies), np.full(21, e0))


def test_ctmc_incremental_energy_tracks_true_energy():
    """The incrementally-maintained CTMC energy must not drift measurably
    from problem.energy over 10k events."""
    prob = _dense_problem(n=16, seed=5, scale=0.4)
    res = run(prob, "ctmc", jax.random.key(1), n_steps=10_000, sample_every=500)
    recorded = np.asarray(res.energies)
    true = np.asarray(jax.vmap(prob.energy)(res.samples))
    np.testing.assert_allclose(recorded, true, atol=5e-3)


def test_ctmc_site_draw_config_and_auto_threshold():
    small = _dense_problem(n=8)
    assert CTMC().resolved_site_draw(small) == "scan"
    big = ising.DenseIsing(
        J=jnp.zeros((sampler_api.TREE_SITE_DRAW_MIN_N,) * 2),
        b=jnp.zeros((sampler_api.TREE_SITE_DRAW_MIN_N,)),
    )
    assert CTMC().resolved_site_draw(big) == "tree"
    assert CTMC(site_draw="scan").resolved_site_draw(big) == "scan"
    with pytest.raises(ValueError, match="site_draw"):
        run(small, CTMC(site_draw="alias"), jax.random.key(0), n_steps=4)
    # auto (scan at this size) is bit-compatible with an explicit scan draw
    r_auto = run(small, "ctmc", jax.random.key(1), n_steps=32, sample_every=4)
    r_scan = run(small, CTMC(site_draw="scan"), jax.random.key(1), n_steps=32, sample_every=4)
    np.testing.assert_array_equal(np.asarray(r_auto.samples), np.asarray(r_scan.samples))


def test_ctmc_tree_draw_chi_square_exact_boltzmann():
    """Acceptance: site_draw='tree' is statistically exact — the
    time-weighted distribution of a long small-n run matches the exact
    Boltzmann law, and the 'scan' path run with the same budget agrees.
    Different random streams, same stationary law."""
    rng = np.random.default_rng(0)
    n = 5
    A = rng.normal(0, 0.7, (n, n))
    J = np.triu(A, 1)
    J = J + J.T
    prob = ising.DenseIsing(
        J=jnp.asarray(J, jnp.float32), b=jnp.asarray(rng.normal(0, 0.4, n), jnp.float32)
    )
    _, p_exact = ising.enumerate_boltzmann(prob)
    p = np.asarray(p_exact, np.float64)
    n_events = 60_000
    dists = {}
    for draw in ("scan", "tree"):
        res = run(prob, CTMC(site_draw=draw), jax.random.key(7),
                  n_steps=n_events, sample_every=1)
        cr = ctmc.CTMCRun.from_result(res)
        dists[draw] = np.asarray(ctmc.time_weighted_distribution(cr, n), np.float64)
    for draw, w in dists.items():
        tv = 0.5 * np.abs(w - p).sum()
        assert tv < 0.03, f"{draw}: TV={tv}"
        # chi-square against the exact law; dwell-time weighting inflates
        # the variance over multinomial, so gate at a generous multiple of
        # the df=31 critical value rather than the 95% quantile.
        chi2 = n_events * float(((w - p) ** 2 / p).sum())
        assert chi2 < 10 * (2 ** n - 1), f"{draw}: chi2={chi2}"
    # and the two paths agree with each other at the same tolerance
    assert 0.5 * np.abs(dists["tree"] - dists["scan"]).sum() < 0.03


def test_ctmc_tree_multi_chain_and_first_hit():
    """The tree draw's (h, tree) aux must survive the driver's vmap and
    first-hit tracking paths."""
    prob = problems.random_maxcut(16, seed=1)
    ref = run(prob, "random_scan_gibbs", jax.random.key(9), n_steps=4000, sample_every=50)
    e_target = float(np.median(np.asarray(ref.energies)))
    res = run(prob, CTMC(site_draw="tree"), jax.random.key(5), n_steps=500,
              n_chains=4, first_hit=e_target)
    assert res.t_hit.shape == (4,) and res.hit.shape == (4,)
    assert np.asarray(res.hit).any()
    assert np.all(np.isfinite(np.asarray(res.t_hit)[np.asarray(res.hit)]))


def test_unroll_event_blocks_bit_parity():
    """Acceptance: batched event-block stepping (run(unroll=K)) must not
    change a single drawn number — keys/betas are pre-split per step, the
    blocks only amortize lax.scan loop overhead. Checked across striding
    (incl. a remainder tail), chains, and both CTMC draw paths."""
    prob = _dense_problem(n=12, seed=3)
    s0 = sampler_api.random_init(jax.random.key(0), (prob.n,))
    for kern in (CTMC(site_draw="tree"), CTMC(site_draw="scan"), TauLeap(dt=0.25)):
        base = run(prob, kern, jax.random.key(1), n_steps=23, s0=s0, sample_every=5)
        for k in (3, 8):
            blocked = run(prob, kern, jax.random.key(1), n_steps=23, s0=s0,
                          sample_every=5, unroll=k)
            np.testing.assert_array_equal(np.asarray(base.s), np.asarray(blocked.s))
            np.testing.assert_array_equal(
                np.asarray(base.samples), np.asarray(blocked.samples)
            )
            np.testing.assert_array_equal(
                np.asarray(base.energies), np.asarray(blocked.energies)
            )
    mc = run(prob, CTMC(site_draw="tree"), jax.random.key(2), n_steps=12, n_chains=3,
             sample_every=4)
    mc_u = run(prob, CTMC(site_draw="tree"), jax.random.key(2), n_steps=12, n_chains=3,
               sample_every=4, unroll=4)
    np.testing.assert_array_equal(np.asarray(mc.samples), np.asarray(mc_u.samples))
    with pytest.raises(ValueError, match="unroll"):
        run(prob, CTMC(), jax.random.key(0), n_steps=4, unroll=0)
    with pytest.raises(ValueError, match="unroll"):
        run(prob, CTMC(), jax.random.key(0), n_steps=4, unroll="fast")


def test_tau_leap_pallas_steps_in_blocks_on_tpu(monkeypatch):
    """run(unroll="auto") unrolls the scan TAU_LEAP_BLOCK_STEPS steps deep
    where each step calls the compiled dense kernel (a TPU); elsewhere, on
    the ref backend and on a lattice it keeps one step. The blocks change
    no drawn number of the batched Pallas run."""
    from repro.kernels import ops

    prob = _grid_exact_problem(denom=128)
    lat = problems.cal_problem(coupling=0.5)
    assert TauLeap(backend="pallas").preferred_unroll(prob) == 1  # interpret mode
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    assert TauLeap(backend="pallas").preferred_unroll(prob) == sampler_api.TAU_LEAP_BLOCK_STEPS
    assert TauLeap().preferred_unroll(prob) == 1
    assert TauLeap(backend="pallas").preferred_unroll(lat) == 1
    monkeypatch.undo()
    kw = dict(n_steps=19, n_chains=3, sample_every=8, first_hit=-60.0, backend="pallas",
              schedule=sampler_api.geometric(0.3, 3.0))
    base = run(prob, TauLeap(dt=0.25), jax.random.key(3), unroll=1, **kw)
    blocked = run(prob, TauLeap(dt=0.25), jax.random.key(3),
                  unroll=sampler_api.TAU_LEAP_BLOCK_STEPS, **kw)
    for field in ("s", "samples", "energies", "t_hit"):
        np.testing.assert_array_equal(
            np.asarray(getattr(base, field)), np.asarray(getattr(blocked, field)), err_msg=field
        )


def test_empty_result_dtypes_match_sampling_mode():
    """Regression: sample_every=0 used to return energies in the STATE
    dtype while the sampling branches return energy-dtype (float32) — the
    empty arrays must concatenate cleanly with sampled ones."""
    prob = _dense_problem(n=8, seed=1)
    for kern in ("ctmc", "random_scan_gibbs", "tau_leap"):
        empty = run(prob, kern, jax.random.key(0), n_steps=8)
        sampled = run(prob, kern, jax.random.key(0), n_steps=8, sample_every=2)
        assert empty.energies.dtype == sampled.energies.dtype, kern
        assert empty.times.dtype == sampled.times.dtype, kern
        assert empty.samples.dtype == sampled.samples.dtype, kern
        # the concatenation downstream report code does must be a no-op
        cat = jnp.concatenate([empty.energies, sampled.energies])
        assert cat.dtype == sampled.energies.dtype


def test_auto_backend_is_ref_off_tpu():
    prob = _dense_problem()
    s0 = sampler_api.random_init(jax.random.key(1), (prob.n,))
    r_auto = run(prob, TauLeap(dt=0.3), jax.random.key(3), n_steps=50, s0=s0, backend="auto")
    r_ref = run(prob, TauLeap(dt=0.3), jax.random.key(3), n_steps=50, s0=s0, backend="ref")
    np.testing.assert_array_equal(np.asarray(r_auto.s), np.asarray(r_ref.s))


def test_multi_chain_with_schedule():
    """Acceptance: n_chains > 1 under a geometric annealing schedule."""
    prob = problems.random_maxcut(24, seed=3)
    n_chains, n_steps = 6, 400
    res = run(
        prob,
        TauLeap(dt=0.25),
        jax.random.key(0),
        n_steps=n_steps,
        n_chains=n_chains,
        schedule=geometric(0.3, 2.5),
        sample_every=40,
    )
    assert res.s.shape == (n_chains, prob.n)
    assert res.samples.shape == (n_chains, n_steps // 40, prob.n)
    assert res.energies.shape == (n_chains, n_steps // 40)
    # chains are independent (per-chain keys): not all identical
    assert len(np.unique(np.asarray(res.s), axis=0)) > 1
    # annealing toward beta=2.5 lowers energy vs the hot start
    e = np.asarray(res.energies)
    assert e[:, -1].mean() < e[:, 0].mean()


def test_per_chain_schedules():
    """(n_chains, n_steps) schedules: the replica-exchange layout. The cold
    chain should end lower in energy than the hot chain on average."""
    prob = problems.sk_instance(16, seed=7)
    betas = jnp.stack(
        [jnp.full((300,), 0.1), jnp.full((300,), 3.0)]
    )
    res = run(
        prob, TauLeap(dt=0.2), jax.random.key(4),
        n_steps=300, n_chains=2, schedule=betas, sample_every=30,
    )
    e = np.asarray(res.energies)
    assert e[1, -5:].mean() < e[0, -5:].mean()
    # a mismatched 2D schedule raises a ValueError naming BOTH numbers up
    # front — not a vmap axis error deep in the driver
    with pytest.raises(ValueError, match=r"2 rows.*n_chains=3"):
        run(prob, TauLeap(dt=0.2), jax.random.key(4), n_steps=300, n_chains=3, schedule=betas)
    with pytest.raises(ValueError, match="n_chains"):
        run(prob, TauLeap(dt=0.2), jax.random.key(4), n_steps=300, schedule=betas)
    with pytest.raises(ValueError, match="shape"):
        run(prob, TauLeap(dt=0.2), jax.random.key(4), n_steps=4, n_chains=2,
            schedule=jnp.ones((2, 2, 4)))
    # resolve_schedule validates directly when handed the chain count
    with pytest.raises(ValueError, match=r"5 rows.*n_chains=4"):
        resolve_schedule(jnp.ones((5, 8)), 8, 4)
    assert resolve_schedule(jnp.ones((4, 8)), 8, 4).shape == (4, 8)


def test_first_hit_multi_chain():
    prob = problems.random_maxcut(16, seed=1)
    ref = run(prob, "random_scan_gibbs", jax.random.key(9), n_steps=4000, sample_every=50)
    e_target = float(np.median(np.asarray(ref.energies)))  # easy target
    res = run(
        prob, "ctmc", jax.random.key(5), n_steps=500, n_chains=4, first_hit=e_target
    )
    assert res.t_hit.shape == (4,) and res.hit.shape == (4,)
    hit = np.asarray(res.hit)
    t_hit = np.asarray(res.t_hit)
    assert np.all(np.isfinite(t_hit[hit]))
    assert np.all(np.isinf(t_hit[~hit]))
    assert hit.any()  # median-energy target is reachable in 500 events


def test_schedule_resolution_forms():
    assert resolve_schedule(None, 5).shape == (5,)
    np.testing.assert_allclose(resolve_schedule(2.0, 3), [2.0, 2.0, 2.0])
    np.testing.assert_allclose(resolve_schedule(constant(0.5), 2), [0.5, 0.5])
    lin = resolve_schedule(linear(0.0, 1.0), 5)
    np.testing.assert_allclose(lin, np.linspace(0, 1, 5), rtol=1e-6)
    geo = np.asarray(resolve_schedule(geometric(0.1, 1.0), 4))
    np.testing.assert_allclose(geo[0], 0.1, rtol=1e-5)
    np.testing.assert_allclose(geo[-1], 1.0, rtol=1e-5)
    with pytest.raises(ValueError):
        resolve_schedule(jnp.ones((7,)), 5)
    with pytest.raises(ValueError):
        sampler_api._resolve_backend("cuda")


def test_timeit_reports_throughput_and_identical_results():
    """run(..., timeit=True) attaches RunTiming without changing results
    (same key both passes) — the benchmark harness hook."""
    prob = _dense_problem(n=10, seed=1)
    s0 = sampler_api.random_init(jax.random.key(0), (prob.n,))
    kw = dict(n_steps=60, s0=s0, sample_every=10)
    plain = run(prob, TauLeap(dt=0.25), jax.random.key(1), **kw)
    timed = run(prob, TauLeap(dt=0.25), jax.random.key(1), timeit=True, **kw)
    assert plain.timing is None
    t = timed.timing
    assert isinstance(t, sampler_api.RunTiming)
    assert t.wall_s > 0 and t.compile_s >= 0
    assert t.steps_per_s == pytest.approx(60 / t.wall_s)
    assert t.chain_steps_per_s == pytest.approx(t.steps_per_s)  # n_chains=1
    np.testing.assert_array_equal(np.asarray(plain.s), np.asarray(timed.s))
    np.testing.assert_array_equal(np.asarray(plain.samples), np.asarray(timed.samples))

    chains = run(
        prob, TauLeap(dt=0.25), jax.random.key(2), n_steps=40, n_chains=3, timeit=True
    )
    assert chains.timing.chain_steps_per_s == pytest.approx(
        3 * chains.timing.steps_per_s
    )


def test_run_error_paths():
    prob = _dense_problem(n=8, seed=0)
    with pytest.raises(KeyError, match="unknown sampler kernel"):
        run(prob, "metropolis_lights_out", jax.random.key(0), n_steps=10)
    with pytest.raises(ValueError, match="backend"):
        run(prob, TauLeap(), jax.random.key(0), n_steps=10, backend="cuda")
    with pytest.raises(ValueError, match="schedule length"):
        run(prob, TauLeap(), jax.random.key(0), n_steps=10, schedule=jnp.ones((7,)))
    with pytest.raises(ValueError):  # 2D schedule without chains
        run(prob, TauLeap(), jax.random.key(0), n_steps=4, schedule=jnp.ones((2, 4)))


def test_legacy_wrappers_are_thin():
    """The deprecated samplers.* entry points must agree bit-for-bit with
    the driver they wrap (beta=1, same per-step key splitting)."""
    prob = _dense_problem(n=8, seed=4)
    s0 = sampler_api.random_init(jax.random.key(0), (prob.n,))
    old = samplers.gibbs_random_scan(prob, jax.random.key(1), s0, n_steps=200, sample_every=10)
    new = run(
        prob, RandomScanGibbs(), jax.random.key(1), n_steps=200, s0=s0, sample_every=10
    )
    np.testing.assert_array_equal(np.asarray(old.s), np.asarray(new.s))
    np.testing.assert_array_equal(np.asarray(old.samples), np.asarray(new.samples))

    lat = problems.cal_problem(coupling=0.5)
    sl0 = sampler_api.random_init(jax.random.key(2), lat.shape)
    old = samplers.chromatic_gibbs(lat, jax.random.key(3), sl0, n_sweeps=15, sample_every=3)
    new = run(lat, ChromaticGibbs(), jax.random.key(3), n_steps=15, s0=sl0, sample_every=3)
    np.testing.assert_array_equal(np.asarray(old.samples), np.asarray(new.samples))


def test_remainder_steps_after_last_observation():
    """n_steps not divisible by sample_every: the tail still advances the
    chain (old traj[k-1::k] semantics)."""
    prob = _dense_problem(n=6, seed=2)
    s0 = sampler_api.random_init(jax.random.key(0), (prob.n,))
    full = run(prob, RandomScanGibbs(), jax.random.key(1), n_steps=17, s0=s0)
    strided = run(
        prob, RandomScanGibbs(), jax.random.key(1), n_steps=17, s0=s0, sample_every=5
    )
    assert strided.samples.shape == (3, prob.n)
    np.testing.assert_array_equal(np.asarray(full.s), np.asarray(strided.s))
    np.testing.assert_allclose(float(strided.t), float(full.t), rtol=1e-6)
