"""Device-fault models (`repro.core.faults`) and their threading through
`sampler_api.run(..., faults=...)`: the faults=None bit-identity guarantee,
per-kernel stuck/noise/dropout semantics, coupling quantization, and the
non-finite-energy guards."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ising, problems, sampler_api
from repro.core.faults import FaultModel, make_stuck, natural_shape, quantize_couplings
from repro.core.sampler_api import CTMC, NonFiniteEnergyError, run
from repro.core.sparse import SparseIsing


def _dense(n=10, seed=0):
    rng = np.random.default_rng(seed)
    J = rng.normal(0, 1.0 / np.sqrt(n), (n, n))
    J = (J + J.T) / 2
    np.fill_diagonal(J, 0)
    b = rng.normal(0, 0.3, n)
    return ising.DenseIsing(J=jnp.asarray(J, jnp.float32), b=jnp.asarray(b, jnp.float32))


def _sparse(n=12, seed=1):
    return problems.random_3regular_maxcut(n, seed=seed)


def _lattice(size=6):
    return problems.get_problem("ferromagnet", size, 0).problem


def _no_stuck(problem):
    """An all-False stuck pair: the faulted code path with zero effect."""
    shape = natural_shape(problem)
    return FaultModel(
        stuck_mask=jnp.zeros(shape, bool), stuck_values=jnp.ones(shape, jnp.float32)
    )


def _stuck(problem, fraction=0.3, seed=5):
    mask, values = make_stuck(jax.random.key(seed), problem, fraction)
    return FaultModel(stuck_mask=mask, stuck_values=values), mask, values


# Every kernel/backend pairing the driver supports, with a tiny problem each.
KERNEL_CASES = [
    ("dense", "random_scan_gibbs", "ref"),
    ("dense", "tau_leap", "ref"),
    ("dense", "tau_leap", "pallas"),
    ("dense", "ctmc_scan", "ref"),
    ("dense", "ctmc_tree", "ref"),
    ("sparse", "ctmc_tree", "ref"),
    ("sparse", "colored_gibbs", "ref"),
    ("sparse", "colored_gibbs", "pallas"),
    ("lattice", "chromatic_gibbs", "ref"),
    ("lattice", "chromatic_gibbs", "pallas"),
    ("lattice", "tau_leap", "ref"),
]


def _case(problem_kind, kernel_name):
    problem = {"dense": _dense, "sparse": _sparse, "lattice": _lattice}[problem_kind]()
    kernel = {
        "ctmc_scan": lambda: CTMC(site_draw="scan"),
        "ctmc_tree": lambda: CTMC(site_draw="tree"),
    }.get(kernel_name, lambda: kernel_name)()
    return problem, kernel


# ---------------------------------------------------------------------------
# The bit-identity guarantee (the tentpole contract)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("problem_kind,kernel_name,backend", KERNEL_CASES)
def test_faults_none_bit_identical_to_zero_fault_path(problem_kind, kernel_name, backend):
    """faults=None compiles the exact pre-fault program: it must match the
    faulted code path with an all-False stuck mask bit for bit (neither
    consumes extra PRNG keys), for every kernel/backend pair. A future edit
    that makes a kernel split keys or reorder draws unconditionally breaks
    this immediately."""
    problem, kernel = _case(problem_kind, kernel_name)
    kw = dict(n_steps=12, sample_every=3, backend=backend, first_hit=-1e9)
    off = run(problem, kernel, jax.random.key(7), **kw)
    on = run(problem, kernel, jax.random.key(7), faults=_no_stuck(problem), **kw)
    for a, b in zip(off[:7], on[:7]):  # s, t, samples, times, energies, t_hit, hit
        if a is None:
            assert b is None
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_faults_none_bit_identical_multi_chain():
    """The guarantee survives the driver's vmap batching."""
    problem = _dense()
    kw = dict(n_steps=10, n_chains=3, sample_every=2)
    off = run(problem, "ctmc", jax.random.key(3), **kw)
    on = run(problem, "ctmc", jax.random.key(3), faults=_no_stuck(problem), **kw)
    np.testing.assert_array_equal(np.asarray(off.samples), np.asarray(on.samples))
    np.testing.assert_array_equal(np.asarray(off.times), np.asarray(on.times))


def test_ctmc_unroll_bit_identity_survives_faults():
    """Event-block unrolling must stay bit-identical with the full fault
    stack threaded through the scan carry (keys are pre-split per step)."""
    problem = _dense()
    faults_kw = dict(quantize_bits=5, field_noise_std=0.3, dropout=0.1)
    f, _, _ = _stuck(problem, 0.2)
    faults = dataclasses.replace(f, **faults_kw)
    kw = dict(n_steps=12, sample_every=3, faults=faults)
    r1 = run(problem, CTMC(site_draw="tree"), jax.random.key(2), unroll=1, **kw)
    r4 = run(problem, CTMC(site_draw="tree"), jax.random.key(2), unroll=4, **kw)
    np.testing.assert_array_equal(np.asarray(r1.samples), np.asarray(r4.samples))
    np.testing.assert_array_equal(np.asarray(r1.times), np.asarray(r4.times))


@pytest.mark.parametrize("problem_kind,kernel_name", [
    ("dense", "tau_leap"), ("lattice", "chromatic_gibbs"),
    ("sparse", "colored_gibbs"),
])
def test_backend_bit_parity_under_faults(problem_kind, kernel_name):
    """ref and pallas must agree bit for bit WITH faults on: both backends
    consume the same fault keys and evaluate the same perturbed decisions
    (u-warping on the pallas side is exact because p_flip < 1)."""
    problem, kernel = _case(problem_kind, kernel_name)
    f, _, _ = _stuck(problem, 0.2)
    faults = dataclasses.replace(f, field_noise_std=0.4, dropout=0.15)
    kw = dict(n_steps=10, sample_every=2, faults=faults)
    r_ref = run(problem, kernel, jax.random.key(9), backend="ref", **kw)
    r_pal = run(problem, kernel, jax.random.key(9), backend="pallas", **kw)
    np.testing.assert_array_equal(np.asarray(r_ref.s), np.asarray(r_pal.s))
    np.testing.assert_array_equal(np.asarray(r_ref.samples), np.asarray(r_pal.samples))


# ---------------------------------------------------------------------------
# Stuck spins: never flip, anywhere
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("problem_kind,kernel_name,backend", KERNEL_CASES)
def test_stuck_sites_never_flip(problem_kind, kernel_name, backend):
    problem, kernel = _case(problem_kind, kernel_name)
    faults, mask, values = _stuck(problem, 0.35)
    res = run(problem, kernel, jax.random.key(1), n_steps=20, sample_every=4,
              backend=backend, faults=faults)
    m = np.asarray(mask)
    v = np.asarray(values)
    np.testing.assert_array_equal(np.asarray(res.s)[m], v[m])
    for sample in np.asarray(res.samples):
        np.testing.assert_array_equal(sample[m], v[m])


def test_stuck_sites_never_flip_multi_chain():
    problem = _sparse()
    faults, mask, values = _stuck(problem, 0.3)
    res = run(problem, CTMC(site_draw="tree"), jax.random.key(4), n_steps=15,
              n_chains=3, sample_every=5, faults=faults)
    m = np.asarray(mask)
    for chain in np.asarray(res.samples).reshape(-1, problem.n):
        np.testing.assert_array_equal(chain[m], np.asarray(values)[m])


def test_lattice_bind_absorbs_stuck_into_clamps():
    """On LatticeIsing the stuck mask folds into the clamp epilogue: the
    residual FaultModel is None and the kernels need no fault handling."""
    lat = _lattice()
    faults, mask, values = _stuck(lat, 0.25)
    bound, residual = faults.bind(lat)
    assert residual is None
    np.testing.assert_array_equal(
        np.asarray(bound.clamp_mask), np.asarray(lat.clamp_mask) | np.asarray(mask)
    )
    m = np.asarray(mask)
    np.testing.assert_array_equal(np.asarray(bound.clamp_value)[m], np.asarray(values)[m])


# ---------------------------------------------------------------------------
# Dropout and field noise
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kernel", ["random_scan_gibbs", "tau_leap", "chromatic_gibbs"])
def test_dropout_one_freezes_the_state(kernel):
    problem = _lattice() if kernel == "chromatic_gibbs" else _dense()
    s0 = sampler_api.random_init(jax.random.key(8), sampler_api.state_shape(problem))
    res = run(problem, kernel, jax.random.key(0), n_steps=15, s0=s0,
              faults=FaultModel(dropout=1.0))
    np.testing.assert_array_equal(np.asarray(res.s), np.asarray(s0))


def test_ctmc_dropout_advances_model_time_without_flips():
    """A dropped CTMC event is a lost pulse, not a paused clock: with
    dropout=1 the state freezes but model time still accumulates."""
    problem = _dense()
    s0 = sampler_api.random_init(jax.random.key(8), (problem.n,))
    res = run(problem, "ctmc", jax.random.key(0), n_steps=20, s0=s0,
              faults=FaultModel(dropout=1.0))
    np.testing.assert_array_equal(np.asarray(res.s), np.asarray(s0))
    assert float(res.t) > 0.0


@pytest.mark.parametrize("kernel_name,problem_kind", [
    ("random_scan_gibbs", "dense"), ("ctmc_tree", "sparse"),
    ("colored_gibbs", "sparse"), ("chromatic_gibbs", "lattice"),
])
def test_field_noise_changes_the_dynamics(kernel_name, problem_kind):
    """Noise must actually reach the decisions (a silently-ignored fault
    would pass every other test here)."""
    problem, kernel = _case(problem_kind, kernel_name)
    kw = dict(n_steps=20, sample_every=2)
    clean = run(problem, kernel, jax.random.key(6), **kw)
    noisy = run(problem, kernel, jax.random.key(6),
                faults=FaultModel(field_noise_std=3.0), **kw)
    assert np.any(np.asarray(clean.samples) != np.asarray(noisy.samples))
    assert np.all(np.isfinite(np.asarray(noisy.energies)))


# ---------------------------------------------------------------------------
# Coupling quantization
# ---------------------------------------------------------------------------


def test_quantize_dense_grid_symmetry_and_zeros():
    problem = _dense(n=8, seed=3)
    q = quantize_couplings(problem, 4)
    J = np.asarray(q.J)
    np.testing.assert_array_equal(J, J.T)  # symmetric layouts stay symmetric
    assert np.all(np.diag(J) == 0.0)  # exact zeros stay exactly zero
    # every value sits on the shared signed 4-bit grid, max-|J| included
    scale = float(np.max(np.abs(np.asarray(problem.J))))
    qmax = 2 ** 3 - 1
    codes = J / (scale / qmax)
    np.testing.assert_allclose(codes, np.round(codes), atol=1e-4)
    assert float(np.max(np.abs(J))) == pytest.approx(scale, rel=1e-6)
    np.testing.assert_array_equal(np.asarray(q.b), np.asarray(problem.b))  # biases untouched


def test_quantize_sparse_keeps_edge_copies_identical():
    sp = _sparse()
    q = quantize_couplings(sp, 3)
    Jq = np.asarray(q.to_dense().J)
    np.testing.assert_array_equal(Jq, Jq.T)  # both copies of each edge agree
    # padding slots stay exactly zero
    pad = np.arange(sp.max_deg)[None, :] >= np.asarray(sp.deg)[:, None]
    assert np.all(np.asarray(q.nbr_w)[pad] == 0.0)


def test_quantize_lattice_and_high_bits_near_identity():
    lat = _lattice()
    q = quantize_couplings(lat, 6)
    assert np.asarray(q.w).shape == np.asarray(lat.w).shape
    fine = quantize_couplings(_dense(n=8, seed=4), 24)
    np.testing.assert_allclose(
        np.asarray(fine.J), np.asarray(_dense(n=8, seed=4).J), rtol=1e-5, atol=1e-6
    )


def test_quantize_bits_validation():
    problem = _dense(n=6)
    for bad in (1, 0, -3, True, "8", 4.0):
        with pytest.raises(ValueError, match="quantize_bits"):
            quantize_couplings(problem, bad)
    with pytest.raises(TypeError, match="quantize"):
        quantize_couplings(object(), 4)


def test_bind_quantize_only_leaves_no_residual():
    """A quantize-only FaultModel is fully static: after bind() the driver
    compiles the exact fault-free program on the rewritten problem."""
    problem = _dense(n=6)
    bound, residual = FaultModel(quantize_bits=4).bind(problem)
    assert residual is None
    assert np.any(np.asarray(bound.J) != np.asarray(problem.J))
    # dense stuck stays dynamic: the residual must survive with quantize cleared
    f, _, _ = _stuck(problem, 0.3)
    bound2, residual2 = dataclasses.replace(f, quantize_bits=4).bind(problem)
    assert residual2 is not None and residual2.quantize_bits is None
    assert residual2.stuck_mask is not None


# ---------------------------------------------------------------------------
# Validation and the non-finite guards
# ---------------------------------------------------------------------------


def test_fault_model_validate_rejects_nonsense():
    problem = _dense(n=6)
    shape = (problem.n,)
    ok_mask = jnp.zeros(shape, bool).at[0].set(True)
    ok_vals = jnp.ones(shape, jnp.float32)
    cases = [
        dict(stuck_mask=ok_mask),  # mask without values
        dict(stuck_values=ok_vals),  # values without mask
        dict(stuck_mask=jnp.zeros((3,), bool), stuck_values=jnp.ones((3,))),  # shape
        dict(stuck_mask=jnp.zeros(shape, jnp.float32), stuck_values=ok_vals),  # dtype
        dict(stuck_mask=ok_mask, stuck_values=0.5 * ok_vals),  # off the ±1 grid
        dict(dropout=1.5),
        dict(dropout=-0.1),
        dict(field_noise_std=-1.0),
        dict(field_noise_std=float("nan")),
        dict(quantize_bits=1),
    ]
    for kw in cases:
        with pytest.raises(ValueError):
            FaultModel(**kw).validate(problem)
    # ...and run() performs the same validation host-side before tracing
    with pytest.raises(ValueError, match="dropout"):
        run(problem, "ctmc", jax.random.key(0), n_steps=2,
            faults=FaultModel(dropout=2.0))


def test_make_stuck_fraction_limits_and_validation():
    problem = _dense(n=20)
    mask0, _ = make_stuck(jax.random.key(0), problem, 0.0)
    assert not np.asarray(mask0).any()
    mask1, vals1 = make_stuck(jax.random.key(0), problem, 1.0)
    assert np.asarray(mask1).all()
    assert np.all(np.isin(np.asarray(vals1), (-1.0, 1.0)))
    with pytest.raises(ValueError, match="fraction"):
        make_stuck(jax.random.key(0), problem, 1.5)


def test_describe_is_json_ready():
    import json

    problem = _dense(n=6)
    f, mask, _ = _stuck(problem, 0.5)
    d = dataclasses.replace(f, quantize_bits=4, field_noise_std=0.1, dropout=0.2).describe()
    assert d["stuck_sites"] == int(np.asarray(mask).sum())
    assert d["quantize_bits"] == 4
    json.dumps(d)
    assert FaultModel().describe() == {}


def test_validate_rejects_non_finite_couplings():
    """Satellite guard: NaN/Inf can no longer hide in a problem definition."""
    n = 6
    J = np.zeros((n, n), np.float32)
    J[0, 1] = J[1, 0] = np.nan
    with pytest.raises(ValueError, match="finite"):
        ising.DenseIsing(J=jnp.asarray(J), b=jnp.zeros(n)).validate()
    sp = _sparse()
    bad = dataclasses.replace(sp, nbr_w=sp.nbr_w.at[0, 0].set(jnp.inf))
    with pytest.raises(ValueError, match="finite"):
        bad.validate()


def test_run_raises_non_finite_energy_error():
    """The run() entry probe: a problem whose energies are NaN/Inf fails
    loudly instead of silently recording NaN trajectories."""
    n = 6
    J = np.zeros((n, n), np.float32)
    J[0, 1] = J[1, 0] = np.inf
    problem = ising.DenseIsing(J=jnp.asarray(J), b=jnp.zeros(n))
    with pytest.raises(NonFiniteEnergyError, match="non-finite"):
        run(problem, "random_scan_gibbs", jax.random.key(0), n_steps=2)
    assert issubclass(NonFiniteEnergyError, ValueError)


def _poisoned(problem, part, value):
    """`problem` with one coupling (`part="coupling"`) or one bias set to
    `value`; the coupling symmetrically where the layout stores it twice."""
    if isinstance(problem, ising.DenseIsing):
        if part == "coupling":
            return dataclasses.replace(problem, J=problem.J.at[0, 1].set(value).at[1, 0].set(value))
        return dataclasses.replace(problem, b=problem.b.at[2].set(value))
    if isinstance(problem, SparseIsing):
        if part == "coupling":
            return dataclasses.replace(problem, nbr_w=problem.nbr_w.at[0, 0].set(value))
        return dataclasses.replace(problem, b=problem.b.at[2].set(value))
    if part == "coupling":
        return dataclasses.replace(problem, w=problem.w.at[0, 2, 2].set(value))
    return dataclasses.replace(problem, b=problem.b.at[2, 2].set(value))


@pytest.mark.parametrize("make", [_dense, _sparse, _lattice], ids=["dense", "sparse", "lattice"])
@pytest.mark.parametrize("part,value", [("coupling", np.inf), ("bias", np.nan)])
def test_run_raises_non_finite_energy_error_on_every_call(make, part, value):
    """Every problem kind, NaN/Inf in a coupling or in the bias: run()
    raises before sampling, and again on a later call with a poisoned
    problem of the same shapes after a finite one — the probe checks the
    values passed on each call, with nothing kept per problem."""
    finite = make()
    with pytest.raises(NonFiniteEnergyError, match="non-finite"):
        run(_poisoned(finite, part, value), "tau_leap", jax.random.key(0), n_steps=2)
    assert np.isfinite(np.asarray(run(finite, "tau_leap", jax.random.key(1), n_steps=2).s)).all()
    with pytest.raises(NonFiniteEnergyError, match="non-finite"):
        run(_poisoned(finite, part, -value), "tau_leap", jax.random.key(2), n_steps=2)


def test_run_probe_skipped_under_trace():
    """run() stays traceable: the non-finite probe is host-side only, so a
    jitted caller (e.g. the tempering loop) must not hit a tracer-bool
    error. Pins the regression caught by test_extensions."""
    problem = _dense(n=6, seed=3)

    @jax.jit
    def jitted(key):
        return run(problem, "random_scan_gibbs", key, n_steps=4).s

    s_jit = jitted(jax.random.key(7))
    s_eager = run(problem, "random_scan_gibbs", jax.random.key(7), n_steps=4).s
    np.testing.assert_array_equal(np.asarray(s_jit), np.asarray(s_eager))
