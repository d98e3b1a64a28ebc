"""Per-kernel allclose sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.ising import king_color_masks
from repro.kernels import dense_field as df
from repro.kernels import lattice_gibbs as lg
from repro.kernels import ops
from repro.kernels import ref
from repro.kernels import tau_leap as tl


def _rand_pm1(key, shape, dtype=jnp.float32):
    return (2 * jax.random.bernoulli(key, 0.5, shape) - 1).astype(dtype)


@pytest.mark.parametrize("B,H,W", [(4, 16, 16), (8, 8, 8), (2, 32, 24), (16, 16, 16)])
def test_lattice_gibbs_kernel_matches_ref(B, H, W):
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
    got = lg.lattice_gibbs_sweep(s, w, b, u, colors, frozen, clampv, interpret=True, block_batch=2)
    want = ref.lattice_gibbs_sweep_ref(s, w, b, u, colors_b, frozen_b, clampv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0, rtol=0)


@pytest.mark.parametrize(
    "B,N,blocks",
    [
        (8, 64, (8, 64, 64)),      # padding path: N < 128
        (128, 128, (128, 128, 128)),
        (64, 300, (64, 128, 128)), # non-divisible N -> padded
        (130, 256, (128, 128, 128)),  # non-divisible B
    ],
)
def test_dense_field_kernel_matches_ref(B, N, blocks):
    bb, bn, bk = blocks
    k = jax.random.split(jax.random.key(1), 3)
    s = _rand_pm1(k[0], (B, N)).astype(jnp.int8)
    J = jax.random.randint(k[1], (N, N), -127, 128, jnp.int32).astype(jnp.int8)
    b = jax.random.normal(k[2], (N,))
    scale = jnp.asarray(0.0173, jnp.float32)
    got = df.dense_field(s, J, b, scale, block_b=bb, block_n=bn, block_k=bk, interpret=True)
    want = ref.dense_field_ref(s, J, b, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize(
    "B,N,per_row,block",
    [
        pytest.param(8, 64, (), 64, id="8-64"),
        pytest.param(32, 200, (), 64, id="32-200"),
        pytest.param(128, 128, (), 64, id="128-128"),
        # per-row operands, as a batched run() passes them
        pytest.param(8, 64, ("scale",), 64, id="8-64-row_scale"),
        pytest.param(32, 200, ("bias",), 64, id="32-200-row_bias"),
        pytest.param(130, 128, ("scale", "bias"), 64, id="130-128-row_scale_bias"),
        # the kernel's own blocks: N padded to 1024, 512 columns by 1024 sites
        pytest.param(8, 1000, ("scale", "bias"), None, id="8-1000-default_blocks"),
    ],
)
def test_tau_leap_kernel_matches_ref(B, N, per_row, block):
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
    blocks = {} if block is None else dict(block_b=block, block_n=block, block_k=block)
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


def test_dense_field_int8_exactness():
    """int8 path is exact integer arithmetic — zero float error vs numpy."""
    rng = np.random.default_rng(0)
    B, N = 16, 96
    s = (2 * rng.integers(0, 2, (B, N)) - 1).astype(np.int8)
    J = rng.integers(-127, 128, (N, N)).astype(np.int8)
    acc = s.astype(np.int64) @ J.T.astype(np.int64)
    got = df.dense_field(
        jnp.asarray(s), jnp.asarray(J), jnp.zeros((N,)), jnp.asarray(1.0, jnp.float32),
        block_b=16, block_n=32, block_k=32, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(got), acc.astype(np.float32))


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
    """mode='kernel' with a batch the block doesn't divide must fail fast
    with a readable ValueError, not an opaque Pallas grid error at trace."""
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
            s, w, b, u, colors, frozen, clampv, mode="kernel", block_batch=4
        )
    # a dividing block is fine
    out = ops.lattice_gibbs_sweep(
        s, w, b, u, colors, frozen, clampv, mode="kernel", block_batch=3
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


@pytest.mark.parametrize("B,n", [(4, 16), (8, 48), (2, 100)])
def test_sparse_fields_kernel_matches_ref(B, n):
    from repro.kernels import sparse_gather as sg

    sp = _rand_sparse_tables(jax.random.key(20), n)
    s = _rand_pm1(jax.random.key(21), (B, n))
    got = sg.sparse_fields(s, sp.nbr_idx, sp.nbr_w, sp.b, interpret=True,
                           block_batch=2)
    want = ref.sparse_fields_ref(s, sp.nbr_idx, sp.nbr_w, sp.b)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # ...and both equal the problem's own local_fields, bit-for-bit
    np.testing.assert_array_equal(np.asarray(want), np.asarray(sp.local_fields(s)))


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
        beta_arr, interpret=True, block_batch=2,
    )
    want = ref.colored_gibbs_sweep_ref(
        s, sp.nbr_idx, sp.nbr_w, sp.b, u, sp.color_masks, beta_arr
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ops_sparse_eager_block_batch_validation():
    """mode='kernel' with a batch the block doesn't divide must fail fast
    with a readable ValueError, not an opaque Pallas grid error."""
    sp = _rand_sparse_tables(jax.random.key(25), 12)
    C = sp.color_masks.shape[0]
    s = jnp.ones((6, 12))
    u = jnp.zeros((C, 6, 12))
    masks = sp.color_masks.astype(jnp.float32)
    with pytest.raises(ValueError, match="block_batch"):
        ops.colored_gibbs_sweep(s, sp.nbr_idx, sp.nbr_w, sp.b, u, masks,
                                mode="kernel", block_batch=4)
    with pytest.raises(ValueError, match="block_batch"):
        ops.sparse_fields(s, sp.nbr_idx, sp.nbr_w, sp.b, mode="kernel", block_batch=5)
    # a dividing block is fine, and matches the reference mode bit-for-bit
    out = ops.colored_gibbs_sweep(s, sp.nbr_idx, sp.nbr_w, sp.b, u, masks,
                                  mode="kernel", block_batch=3)
    want = ops.colored_gibbs_sweep(s, sp.nbr_idx, sp.nbr_w, sp.b, u, masks,
                                   mode="reference")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_ops_auto_uses_reference_on_cpu():
    """ops.* 'auto' mode must agree with the kernel path bit-for-bit."""
    B, N = 8, 64
    k = jax.random.split(jax.random.key(6), 3)
    s = _rand_pm1(k[0], (B, N)).astype(jnp.int8)
    J = jax.random.randint(k[1], (N, N), -127, 128, jnp.int32).astype(jnp.int8)
    b = jax.random.normal(k[2], (N,))
    scale = jnp.asarray(0.01, jnp.float32)
    auto = ops.dense_field(s, J, b, scale)
    kern = ops.dense_field(s, J, b, scale, mode="kernel", block_b=8, block_n=64, block_k=64)
    np.testing.assert_allclose(np.asarray(auto), np.asarray(kern), rtol=1e-6, atol=1e-5)


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
