"""The sampler's Pallas kernels compile for a TPU v5e at deployment sizes.

No chip is needed: the TPU compiler is installed with jax, and it compiles
for a described `v5e:2x2` topology that is not attached. Interpret-mode
tests cannot see what Mosaic refuses (operand types the MXU does not take,
gathers it cannot lower, more VMEM than a kernel may use); these can.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file. Each test asserts that the compiled program holds the kernel
(`tpu_custom_call`), i.e. that nothing fell back to interpret mode.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.core.ising import N_KING_COLORS
from repro.kernels import dense_field, lattice_gibbs, sparse_gather, tau_leap


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip is written to the persistent cache but
    # can never be read back without the chip: keep the cache out of it.
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding) for shape, dtype in specs]


def _assert_kernel_compiles(fn, args, **static):
    compiled = fn.lower(*args, interpret=False, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()


F32, I8, I32 = jnp.float32, jnp.int8, jnp.int32


def test_tau_leap_step_compiles_n2048_b128(one_chip):
    B, N = 128, 2048
    args = _shapes(
        one_chip, ((B, N), F32), ((N, N), I8), ((N,), F32), ((), F32), ((B, N), F32), ((), F32)
    )
    _assert_kernel_compiles(tau_leap.tau_leap_step, args)


def test_tau_leap_step_per_row_scale_bias_compiles_n2048_b128(one_chip):
    """The per-row operands a batched run() passes: a (B,) scale, (B, N) bias."""
    B, N = 128, 2048
    args = _shapes(
        one_chip, ((B, N), F32), ((N, N), I8), ((B, N), F32), ((B,), F32), ((B, N), F32), ((), F32)
    )
    _assert_kernel_compiles(tau_leap.tau_leap_step, args)


def test_dense_field_compiles_n2048_b128(one_chip):
    B, N = 128, 2048
    args = _shapes(one_chip, ((B, N), I8), ((N, N), I8), ((N,), F32), ((), F32))
    _assert_kernel_compiles(dense_field.dense_field, args)


def test_lattice_gibbs_sweep_compiles_128x128_b8(one_chip):
    B, H, W = 8, 128, 128
    args = _shapes(
        one_chip,
        ((B, H, W), F32), ((8, H, W), F32), ((H, W), F32),
        ((N_KING_COLORS, B, H, W), F32), ((N_KING_COLORS, H, W), F32),
        ((H, W), F32), ((H, W), F32), ((), F32),
    )
    _assert_kernel_compiles(lattice_gibbs.lattice_gibbs_sweep, args, block_batch=8)


# n=4096 with max_deg 8 (a king's-graph degree) and a 4-coloring
SPARSE_B, SPARSE_N, SPARSE_MD, SPARSE_C = 8, 4096, 8, 4


def test_colored_gibbs_sweep_compiles_n4096_deg8_b8(one_chip):
    B, n, md, C = SPARSE_B, SPARSE_N, SPARSE_MD, SPARSE_C
    args = _shapes(
        one_chip,
        ((B, n), F32), ((n, md), I32), ((n, md), F32), ((n,), F32),
        ((C, B, n), F32), ((C, n), F32), ((), F32),
    )
    _assert_kernel_compiles(sparse_gather.colored_gibbs_sweep, args, block_batch=8)


def test_sparse_fields_compiles_n4096_deg8_b8(one_chip):
    B, n, md = SPARSE_B, SPARSE_N, SPARSE_MD
    args = _shapes(one_chip, ((B, n), F32), ((n, md), I32), ((n, md), F32), ((n,), F32))
    _assert_kernel_compiles(sparse_gather.sparse_fields, args, block_batch=8)
