"""Per-kernel allclose sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.ising import king_color_masks
from repro.kernels import lattice_gibbs as lg
from repro.kernels import ops
from repro.kernels import ref
from repro.kernels import tau_leap as tl


def _rand_pm1(key, shape, dtype=jnp.float32):
    return (2 * jax.random.bernoulli(key, 0.5, shape) - 1).astype(dtype)


LATTICE_TILES = [(4, 16, 16, None), (8, 8, 8, None), (2, 32, 24, None), (16, 16, 16, None),
                 (4, 16, 16, 8), (2, 32, 24, 8), (2, 32, 24, 16), (2, 40, 16, 16),
                 (3, 30, 12, 8), (3, 30, 12, 16)]


@pytest.mark.parametrize(
    "B,H,W,block_rows", LATTICE_TILES,
    ids=[f"{B}-{H}-{W}" + (f"-tile{t}" if t else "") for B, H, W, t in LATTICE_TILES],
)
def test_lattice_gibbs_kernel_matches_ref(B, H, W, block_rows):
    """Bit-parity with the oracle at every tile height: one tile (None, the
    VMEM budget's choice at these sizes), 8 and 16 rows; H = 40 and 30 leave
    the last tile short."""
    k = jax.random.split(jax.random.key(0), 5)
    s = _rand_pm1(k[0], (B, H, W))
    w = jax.random.normal(k[1], (8, H, W)) * 0.5
    b = jax.random.normal(k[2], (H, W)) * 0.3
    u = jax.random.uniform(k[3], (4, B, H, W))
    colors_b = king_color_masks(H, W)
    colors = colors_b.astype(jnp.float32)
    frozen_b = jax.random.bernoulli(k[4], 0.2, (H, W))
    frozen = frozen_b.astype(jnp.float32)
    clampv = _rand_pm1(jax.random.key(9), (H, W))

    # NOTE: w here is asymmetric (not a valid Ising problem) — fine for the
    # kernel-vs-oracle comparison, which is pure arithmetic.
    got = lg.lattice_gibbs_sweep(
        s, w, b, u, colors, frozen, clampv, interpret=True, block_batch=1 if B == 3 else 2,
        block_rows=block_rows,
    )
    want = ref.lattice_gibbs_sweep_ref(s, w, b, u, colors_b, frozen_b, clampv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0, rtol=0)


@pytest.mark.parametrize("block_rows", [8, 16, 24])
def test_lattice_gibbs_tiles_with_faults_match_ref(block_rows):
    """Chains mapped as `run()` maps them, with what faults give the kernel:
    per-chain field noise in the bias, per-chain drops in the update masks,
    dead sites (frozen, read as -1) and clamped sites (frozen at ±1). H = 40
    gives 5 tiles of 8 rows, a short last tile of 16 and of 24 rows; tile
    edges fall between an odd row above and an even row below, so both row
    parities meet the halo."""
    A, H, W = 3, 40, 16
    k = jax.random.split(jax.random.key(21), 8)
    s = _rand_pm1(k[0], (A, 1, H, W))
    w = jax.random.normal(k[1], (8, H, W)) * 0.5
    noisy_b = jax.random.normal(k[2], (H, W)) * 0.3 + 0.2 * jax.random.normal(k[3], (A, H, W))
    u = jax.random.uniform(k[4], (A, 4, 1, H, W))
    keep = jax.random.bernoulli(k[5], 0.8, (A, 1, H, W))
    update = king_color_masks(H, W)[None] & keep  # (A, 4, H, W)
    dead = jax.random.bernoulli(k[6], 0.1, (H, W))
    clamped = jax.random.bernoulli(k[7], 0.1, (H, W)) & ~dead
    frozen = dead | clamped
    clampv = jnp.where(dead, -1.0, _rand_pm1(jax.random.key(22), (H, W)))
    beta = jnp.asarray([0.4, 1.0, 2.5], jnp.float32)

    def kernel(s, b, u, upd, bt):
        return ops.lattice_gibbs_sweep(
            s, w, b, u, upd.astype(jnp.float32), frozen.astype(jnp.float32), clampv, bt,
            block_batch=1, block_rows=block_rows,
        )

    def oracle(s, b, u, upd, bt):
        return ref.lattice_gibbs_sweep_ref(s, w, b, u, upd, frozen, clampv, bt)

    got = jax.vmap(kernel)(s, noisy_b, u, update, beta)
    want = jax.vmap(oracle)(s, noisy_b, u, update, beta)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(jnp.sum(got != s)) > 0
    np.testing.assert_array_equal(np.asarray(got)[:, 0][:, np.asarray(dead)], -1.0)


def test_lattice_gibbs_tile_rows():
    """Tile heights come from the VMEM budget: 64 rows at 384 x 384 with one
    chain per block, fewer at 1024 wide; a height off the 8-row grid is refused."""
    assert lg.tile_rows(384, 384, 1) == 64
    assert lg.tile_rows(1024, 1024, 1) == 16
    assert lg.tile_rows(128, 128, 8) == 64
    assert lg.tile_rows(12, 12, 4) == 16
    B, H, W = 1, 16, 16
    with pytest.raises(ValueError, match="block_rows"):
        lg.lattice_gibbs_sweep(
            jnp.ones((B, H, W)), jnp.zeros((8, H, W)), jnp.zeros((H, W)),
            jnp.zeros((4, B, H, W)), king_color_masks(H, W).astype(jnp.float32),
            jnp.zeros((H, W)), jnp.ones((H, W)), block_rows=12,
        )


@pytest.mark.parametrize("chains", [1, 5])
def test_lattice_gibbs_calls_noted(chains):
    """`run()` on the lattice notes one one-chain call per chain and sweep."""
    from repro.core import problems, sampler_api, tracing

    problem = problems.get_problem("ferromagnet", 8).problem
    jax.clear_caches()
    sampler_api.run(problem, "chromatic_gibbs", jax.random.key(0), n_steps=2,
                    n_chains=chains, backend="pallas")
    assert tracing.row_occupancy("lattice_gibbs_sweep") == (1, 1, chains)


@pytest.mark.parametrize(
    "B,N,per_row,block",
    [
        pytest.param(8, 64, (), (64, 64, 64), id="8-64"),
        pytest.param(32, 200, (), (64, 64, 64), id="32-200"),
        pytest.param(128, 128, (), (64, 64, 64), id="128-128"),
        # per-row operands, as a batched run() passes them
        pytest.param(8, 64, ("scale",), (64, 64, 64), id="8-64-row_scale"),
        pytest.param(32, 200, ("bias",), (64, 64, 64), id="32-200-row_bias"),
        pytest.param(130, 128, ("scale", "bias"), (64, 64, 64), id="130-128-row_scale_bias"),
        # the kernel's own blocks: N padded to 1024, 512 columns by 1024 sites
        pytest.param(8, 1000, ("scale", "bias"), None, id="8-1000-default_blocks"),
        # the int8 matmul's tiling: N below one block, one block, N padded,
        # B not a multiple of block_b, several k blocks accumulated in int32
        pytest.param(8, 64, (), (8, 64, 64), id="8-64-blocks8x64x64"),
        pytest.param(128, 128, (), (128, 128, 128), id="128-128-blocks128"),
        pytest.param(64, 300, (), (64, 128, 128), id="64-300-blocks64x128x128"),
        pytest.param(130, 256, (), (128, 128, 128), id="130-256-blocks128"),
        pytest.param(16, 96, (), (16, 32, 32), id="16-96-blocks16x32x32"),
    ],
)
def test_tau_leap_kernel_matches_ref(B, N, per_row, block):
    """Bit-parity with the int32-accumulating oracle at every blocking."""
    k = jax.random.split(jax.random.key(2), 6)
    s = _rand_pm1(k[0], (B, N))
    J = jax.random.randint(k[1], (N, N), -127, 128, jnp.int32).astype(jnp.int8)
    b = jax.random.normal(k[2], (N,)) * 0.2
    u = jax.random.uniform(k[3], (B, N))
    scale = jnp.asarray(1.0 / 127.0, jnp.float32)
    dt = jnp.asarray(0.3, jnp.float32)
    if "scale" in per_row:
        scale = scale * jax.random.uniform(k[4], (B,), minval=0.1, maxval=3.0)
    if "bias" in per_row:
        b = b + jax.random.normal(k[5], (B, N)) * 0.5
    blocks = {} if block is None else dict(zip(("block_b", "block_n", "block_k"), block))
    got = tl.tau_leap_step(s, J, b, scale, u, dt, interpret=True, **blocks)
    want = ref.tau_leap_step_ref(s, J, b, scale, u, dt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0)


@pytest.mark.parametrize("C", [1, 3, 128])
def test_tau_leap_row_occupancy(C):
    """run() on C chains puts them in the rows of ONE kernel call per step:
    C of 128 rows, where each chain used to be 1 of 128 rows in a call of
    its own. The note is made while the program is traced, so each case
    runs a shape no other test runs."""
    from repro.core import ising, sampler_api, tracing

    n = 40
    J = jax.random.randint(jax.random.key(4), (n, n), -127, 128, jnp.int32) / 127.0
    prob = ising.DenseIsing(J=jnp.triu(J, 1) + jnp.triu(J, 1).T, b=jnp.zeros((n,)))
    sampler_api.run(prob, sampler_api.TauLeap(dt=0.25), jax.random.key(0), n_steps=5,
                    n_chains=C, backend="pallas")
    assert tracing.row_occupancy("tau_leap_step") == tracing.RowOccupancy(C, 128, 1)


def test_quantize_dense_roundtrip():
    rng = np.random.default_rng(3)
    J = jnp.asarray(rng.normal(0, 0.5, (40, 40)), jnp.float32)
    codes, scale = ops.quantize_dense(J, 8)
    deq = codes.astype(jnp.float32) * scale
    assert float(jnp.max(jnp.abs(deq - J))) <= float(scale) / 2 + 1e-6
    assert codes.dtype == jnp.int8


@pytest.mark.parametrize("beta", [0.3, 1.0, 3.0])
def test_lattice_gibbs_kernel_matches_ref_beta(beta):
    """Beta-threaded sweep: ref <-> pallas(interpret) bit-parity at every
    scheduled inverse temperature, with frozen AND clamp masks active."""
    B, H, W = 4, 12, 12
    k = jax.random.split(jax.random.key(11), 6)
    s = _rand_pm1(k[0], (B, H, W))
    w = jax.random.normal(k[1], (8, H, W)) * 0.5
    b = jax.random.normal(k[2], (H, W)) * 0.3
    u = jax.random.uniform(k[3], (4, B, H, W))
    colors_b = king_color_masks(H, W)
    frozen_b = jax.random.bernoulli(k[4], 0.25, (H, W))
    clampv = _rand_pm1(k[5], (H, W))
    beta_arr = jnp.asarray(beta, jnp.float32)

    got = lg.lattice_gibbs_sweep(
        s, w, b, u, colors_b.astype(jnp.float32), frozen_b.astype(jnp.float32),
        clampv, beta_arr, interpret=True, block_batch=2,
    )
    want = ref.lattice_gibbs_sweep_ref(s, w, b, u, colors_b, frozen_b, clampv, beta_arr)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # frozen sites read the clamp value regardless of beta
    np.testing.assert_array_equal(
        np.asarray(got)[:, np.asarray(frozen_b)],
        np.broadcast_to(np.asarray(clampv)[np.asarray(frozen_b)], (B, int(frozen_b.sum()))),
    )


def test_lattice_gibbs_beta_default_is_one():
    """Omitting beta must reproduce the historical beta=1 arithmetic."""
    B, H, W = 2, 8, 8
    k = jax.random.split(jax.random.key(12), 4)
    s = _rand_pm1(k[0], (B, H, W))
    w = jax.random.normal(k[1], (8, H, W)) * 0.5
    b = jax.random.normal(k[2], (H, W)) * 0.3
    u = jax.random.uniform(k[3], (4, B, H, W))
    colors_b = king_color_masks(H, W)
    frozen = jnp.zeros((H, W))
    clampv = -jnp.ones((H, W))
    got_none = lg.lattice_gibbs_sweep(
        s, w, b, u, colors_b.astype(jnp.float32), frozen, clampv, interpret=True
    )
    got_one = lg.lattice_gibbs_sweep(
        s, w, b, u, colors_b.astype(jnp.float32), frozen, clampv,
        jnp.asarray(1.0, jnp.float32), interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(got_none), np.asarray(got_one))


def test_ops_lattice_gibbs_eager_block_batch_validation():
    """A batch the block doesn't divide must fail fast with a readable
    ValueError, not an opaque Pallas grid error at trace."""
    B, H, W = 6, 8, 8
    s = jnp.ones((B, H, W))
    w = jnp.zeros((8, H, W))
    b = jnp.zeros((H, W))
    u = jnp.zeros((4, B, H, W))
    colors = king_color_masks(H, W).astype(jnp.float32)
    frozen = jnp.zeros((H, W))
    clampv = jnp.ones((H, W))
    with pytest.raises(ValueError, match="block_batch"):
        ops.lattice_gibbs_sweep(
            s, w, b, u, colors, frozen, clampv, block_batch=4
        )
    # a dividing block is fine
    out = ops.lattice_gibbs_sweep(
        s, w, b, u, colors, frozen, clampv, block_batch=3
    )
    assert out.shape == (B, H, W)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lattice_gibbs_dtype_sweep(dtype):
    B, H, W = 4, 16, 16
    k = jax.random.split(jax.random.key(5), 5)
    s = _rand_pm1(k[0], (B, H, W), dtype)
    w = (jax.random.normal(k[1], (8, H, W)) * 0.5).astype(dtype)
    b = (jax.random.normal(k[2], (H, W)) * 0.3).astype(dtype)
    u = jax.random.uniform(k[3], (4, B, H, W)).astype(dtype)
    colors = king_color_masks(H, W).astype(dtype)
    frozen = jnp.zeros((H, W), dtype)
    clampv = -jnp.ones((H, W), dtype)
    got = lg.lattice_gibbs_sweep(s, w, b, u, colors, frozen, clampv, interpret=True, block_batch=4)
    want = ref.lattice_gibbs_sweep_ref(
        s, w, b, u, colors > 0.5, frozen > 0.5, clampv
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=0
    )


def _rand_sparse_tables(key, n, density=0.4):
    """Random symmetric sparse couplings in padded neighbor-list layout,
    plus a greedy coloring — built through SparseIsing so the tables obey
    the padding convention the kernels assume."""
    from repro.core import ising as _ising
    from repro.core.sparse import SparseIsing

    k1, k2 = jax.random.split(key)
    A = jax.random.normal(k1, (n, n)) * 0.5
    mask = jax.random.bernoulli(k2, density, (n, n))
    J = jnp.triu(A * mask, k=1)
    J = J + J.T
    b = jax.random.normal(jax.random.key(99), (n,)) * 0.3
    return SparseIsing.from_dense(_ising.DenseIsing(J=J.astype(jnp.float32),
                                                    b=b.astype(jnp.float32)))


@pytest.mark.parametrize("beta", [None, 0.3, 1.0, 3.0])
def test_colored_gibbs_kernel_matches_ref_beta(beta):
    """Colored sweep: ref <-> pallas(interpret) bit-parity at every
    scheduled inverse temperature (None -> the historical beta=1 path)."""
    from repro.kernels import sparse_gather as sg

    B, n = 4, 32
    sp = _rand_sparse_tables(jax.random.key(22), n)
    C = sp.color_masks.shape[0]
    s = _rand_pm1(jax.random.key(23), (B, n))
    u = jax.random.uniform(jax.random.key(24), (C, B, n))
    beta_arr = None if beta is None else jnp.asarray(beta, jnp.float32)
    got = sg.colored_gibbs_sweep(
        s, sp.nbr_idx, sp.nbr_w, sp.b, u, sp.color_masks.astype(jnp.float32),
        beta_arr, interpret=True,
    )
    want = ref.colored_gibbs_sweep_ref(
        s, sp.nbr_idx, sp.nbr_w, sp.b, u, sp.color_masks, beta_arr
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ops_sparse_eager_block_batch_validation():
    """A block the kernel cannot take must fail fast with a readable
    ValueError, not an opaque Pallas grid error: the colored sweep's lane
    block (its chains) must be a multiple of 128 lanes."""
    sp = _rand_sparse_tables(jax.random.key(25), 12)
    C = sp.color_masks.shape[0]
    s = jnp.ones((6, 12))
    u = jnp.zeros((C, 6, 12))
    masks = sp.color_masks.astype(jnp.float32)
    with pytest.raises(ValueError, match="block_lanes"):
        ops.colored_gibbs_sweep(s, sp.nbr_idx, sp.nbr_w, sp.b, u, masks, block_lanes=4)
    # a whole lane block is fine, and matches the oracle bit-for-bit
    out = ops.colored_gibbs_sweep(s, sp.nbr_idx, sp.nbr_w, sp.b, u, masks, block_lanes=128)
    want = ref.colored_gibbs_sweep_ref(s, sp.nbr_idx, sp.nbr_w, sp.b, u, sp.color_masks)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def _irregular_graph(n, seed):
    """A sparse graph of degrees 1 to 5 (so most sites have padded slots),
    random ±weights and biases, greedily colored."""
    from repro.core.sparse import SparseIsing

    rng = np.random.default_rng(seed)
    edges = {(i, (i + 1) % n) for i in range(n)}
    while len(edges) < 2 * n:
        i, j = sorted(rng.choice(n, 2, replace=False))
        edges.add((int(i), int(j)))
    deg = np.zeros(n, int)
    kept = []
    for i, j in sorted(edges):
        if deg[i] < 5 and deg[j] < 5:
            kept.append((i, j, float(rng.normal())))
            deg[i] += 1
            deg[j] += 1
    sp = SparseIsing.from_edges(n, kept)
    return dataclasses.replace(sp, b=jnp.asarray(rng.normal(size=n) * 0.3, jnp.float32))


# chains: 1, 3, 64 and 130 (two lane blocks); n: below a block of 8, not a
# multiple of 8, and not of 128
SWEEP_SHAPES = [(1, 5), (3, 37), (64, 29), (130, 133)]


@pytest.mark.parametrize("B,n", SWEEP_SHAPES, ids=[f"B{B}-n{n}" for B, n in SWEEP_SHAPES])
def test_colored_gibbs_sweep_shapes_match_ref(B, n):
    """Chains in lanes: bit-parity with the oracle for any chain count and
    any n, at irregular degree with padded slots."""
    from repro.kernels import sparse_gather as sg

    sp = _irregular_graph(n, seed=n)
    assert int(np.min(np.asarray(sp.deg))) < sp.max_deg  # padded slots occur
    C = sp.color_masks.shape[0]
    s = _rand_pm1(jax.random.key(B), (B, n))
    u = jax.random.uniform(jax.random.key(n), (C, B, n))
    beta = jnp.asarray(0.8, jnp.float32)
    got = sg.colored_gibbs_sweep(
        s, sp.nbr_idx, sp.nbr_w, sp.b, u, sp.color_masks.astype(jnp.float32), beta,
        interpret=True,
    )
    want = ref.colored_gibbs_sweep_ref(s, sp.nbr_idx, sp.nbr_w, sp.b, u, sp.color_masks, beta)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert 0 < float(jnp.mean(got != s)) < 1  # the sweep did flip spins


VMAP_CASES = ["shared_beta", "chain_beta", "chain_bias", "chain_masks"]


@pytest.mark.parametrize("case", VMAP_CASES)
def test_colored_gibbs_vmap_matches_ref(case):
    """Under `jax.vmap` of one-chain calls, as `run()` makes them, the A
    calls become one call of A lanes; a per-chain beta, bias or update mask
    becomes a per-lane operand. Each chain's result equals the oracle's."""
    from repro.core import tracing

    A, n = 5, 23
    sp = _irregular_graph(n, seed=3)
    C = sp.color_masks.shape[0]
    keys = jax.random.split(jax.random.key(7), 4)
    s = _rand_pm1(keys[0], (A, 1, n))
    u = jax.random.uniform(keys[1], (A, C, 1, n))
    beta = jnp.asarray(0.6, jnp.float32)
    b = sp.b
    masks = sp.color_masks
    axes = [0, None, None, None, 0, None, None]
    if case == "chain_beta":
        beta, axes[6] = jnp.linspace(0.2, 2.0, A, dtype=jnp.float32), 0
    if case == "chain_bias":
        b = sp.b + 0.5 * jax.random.normal(keys[2], (A, 1, n))
        axes[3] = 0
    if case == "chain_masks":  # drops: each chain keeps its own sites
        keep = jax.random.bernoulli(keys[3], 0.7, (A, 1, n))
        masks, axes[5] = sp.color_masks[None, :, None] & keep[:, None], 0
    kernel = ops.colored_gibbs_sweep
    oracle = lambda s, i, w, b, u, m, bt: ref.colored_gibbs_sweep_ref(s, i, w, b, u, m > 0.5, bt)
    args = (s, sp.nbr_idx, sp.nbr_w, b, u, masks.astype(jnp.float32), beta)
    got = jax.vmap(kernel, in_axes=axes)(*args)
    assert tracing.row_occupancy("colored_gibbs_sweep") == tracing.RowOccupancy(A, 128, 1)
    want = jax.vmap(oracle, in_axes=axes)(*args)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("A", [1, 2, 6])
def test_colored_gibbs_row_occupancy(A):
    """run() on A chains puts them in the lanes of ONE kernel call per
    sweep: A of 128 lanes, where each chain used to be a call of its own;
    one chain is not mapped and is noted the same way. The note is made
    while the program is traced, so each case runs a shape no other test
    runs."""
    from repro.core import sampler_api, tracing

    sp = _irregular_graph(19, seed=A)
    sampler_api.run(sp, sampler_api.ColoredGibbs(), jax.random.key(0), n_steps=3,
                    n_chains=A, backend="pallas")
    assert tracing.row_occupancy("colored_gibbs_sweep") == tracing.RowOccupancy(A, 128, 1)


FALLBACK_KERNELS = ["tau_leap_step", "colored_gibbs_sweep", "lattice_gibbs_sweep"]


@pytest.mark.parametrize("kernel", FALLBACK_KERNELS)
def test_chains_rule_falls_back_when_a_shared_operand_is_mapped(kernel):
    """A map over an operand the kernel reads once for all its chains (the
    couplings, the neighbour weights, the lattice's weight planes) takes
    vmap's default rule: one call per mapped element, noted as A calls,
    each equal to the oracle."""
    from repro.core import tracing

    A = 3
    ks = jax.random.split(jax.random.key(31), 5)
    if kernel == "tau_leap_step":
        N = 40
        J = jax.random.randint(ks[1], (A, N, N), -127, 128, jnp.int32).astype(jnp.int8)
        args = (_rand_pm1(ks[0], (A, 1, N)), J, jax.random.normal(ks[2], (N,)) * 0.2,
                jnp.asarray(1.0 / 127.0, jnp.float32), jax.random.uniform(ks[3], (A, 1, N)),
                jnp.asarray(0.3, jnp.float32))
        axes = (0, 0, None, None, 0, None)
        op, oracle, tile = ops.tau_leap_step, ref.tau_leap_step_ref, 128
    elif kernel == "colored_gibbs_sweep":
        sp = _irregular_graph(17, seed=4)
        C, n = sp.color_masks.shape
        w = sp.nbr_w * jnp.arange(1.0, A + 1.0)[:, None, None]
        args = (_rand_pm1(ks[0], (A, 1, n)), sp.nbr_idx, w, sp.b,
                jax.random.uniform(ks[1], (A, C, 1, n)), sp.color_masks.astype(jnp.float32),
                jnp.asarray(0.7, jnp.float32))
        axes = (0, None, 0, None, 0, None, None)
        op, tile = ops.colored_gibbs_sweep, 128
        oracle = lambda s, i, w, b, u, m, bt: ref.colored_gibbs_sweep_ref(s, i, w, b, u, m > 0.5, bt)
    else:
        H = W = 16
        frozen = jax.random.bernoulli(ks[4], 0.2, (H, W))
        args = (_rand_pm1(ks[0], (A, 1, H, W)), jax.random.normal(ks[1], (A, 8, H, W)) * 0.5,
                jax.random.normal(ks[2], (H, W)) * 0.3, jax.random.uniform(ks[3], (A, 4, 1, H, W)),
                king_color_masks(H, W).astype(jnp.float32), frozen.astype(jnp.float32),
                _rand_pm1(jax.random.key(32), (H, W)), jnp.asarray(0.9, jnp.float32))
        axes = (0, 0, None, 0, None, None, None, None)
        op, tile = ops.lattice_gibbs_sweep, 1
        oracle = lambda s, w, b, u, c, f, cv, bt: ref.lattice_gibbs_sweep_ref(
            s, w, b, u, c > 0.5, f > 0.5, cv, bt)
    got = jax.vmap(op, in_axes=axes)(*args)
    assert tracing.row_occupancy(kernel) == tracing.RowOccupancy(1, tile, A)
    want = jax.vmap(oracle, in_axes=axes)(*args)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize(
    "BH,Sq,Sk,d,causal,dtype",
    [
        (2, 256, 256, 64, True, jnp.float32),
        (4, 128, 384, 32, False, jnp.float32),
        (1, 512, 512, 128, True, jnp.bfloat16),
        (2, 256, 256, 64, True, jnp.bfloat16),
    ],
)
def test_flash_attention_matches_ref(BH, Sq, Sk, d, causal, dtype):
    from repro.kernels import flash_attention as fa

    ks = jax.random.split(jax.random.key(7), 3)
    q = (jax.random.normal(ks[0], (BH, Sq, d)) * 0.5).astype(dtype)
    k = (jax.random.normal(ks[1], (BH, Sk, d)) * 0.5).astype(dtype)
    v = (jax.random.normal(ks[2], (BH, Sk, d)) * 0.5).astype(dtype)
    got = fa.flash_attention(q, k, v, causal=causal, block_q=128, block_k=128, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol, rtol=tol
    )
