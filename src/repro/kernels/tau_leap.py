"""Pallas TPU kernel: fused dense tau-leap PASS update step.

One asynchronous-model step for a dense problem, fully fused: int8 MXU
field matmul -> flip rates -> Bernoulli flips -> new state, with the spin
update applied in the matmul epilogue (fields never round-trip to HBM).
This is the throughput kernel for large SK/MaxCut sampling sweeps; the
chip analogue is "synapse + neuron + latch" operating concurrently.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dense_field import _pad_to


def _tau_leap_kernel(
    s_mat_ref,   # (BB, BK) int8 — matmul operand (k-indexed block of spins)
    jt_ref,      # (BK, BN) int8
    b_ref,       # (1, BN) f32
    s_out_ref,   # (BB, BN) f32 — current spins at the OUTPUT block
    u_ref,       # (BB, BN) f32 uniforms
    scale_ref,   # (1,) f32 SMEM
    dt_ref,      # (1,) f32 SMEM
    out_ref,     # (BB, BN) f32 new spins
    acc_ref,     # (BB, BN) int32 scratch
    *,
    nk: int,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        s_mat_ref[...],
        jt_ref[...],
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == nk - 1)
    def _epilogue():
        h = acc_ref[...].astype(jnp.float32) * scale_ref[0] + b_ref[...]
        s = s_out_ref[...]
        rate = jax.nn.sigmoid(2.0 * h * s)
        p_flip = 1.0 - jnp.exp(-dt_ref[0] * rate)
        out_ref[...] = jnp.where(u_ref[...] < p_flip, -s, s)


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_n", "block_k", "interpret")
)
def tau_leap_step(
    s: jax.Array,        # (B, N) f32 ±1
    j_i8: jax.Array,     # (N, N) int8
    b: jax.Array,        # (N,) f32
    scale: jax.Array,    # () f32
    uniforms: jax.Array, # (B, N) f32
    dt: jax.Array,       # () f32
    *,
    block_b: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = True,
) -> jax.Array:
    B, N = s.shape
    s_i8 = s.astype(jnp.int8)
    s_i8p = _pad_to(_pad_to(s_i8, 0, block_b), 1, block_k)
    s_fp = _pad_to(_pad_to(s, 0, block_b), 1, block_n)
    u_p = _pad_to(_pad_to(uniforms, 0, block_b), 1, block_n)
    jt_p = _pad_to(_pad_to(j_i8.T, 0, block_k), 1, block_n)
    b_p = _pad_to(b[None, :], 1, block_n)
    Bp, Kp = s_i8p.shape
    _, Np = jt_p.shape
    nk = Kp // block_k
    grid = (Bp // block_b, Np // block_n, nk)
    out = pl.pallas_call(
        functools.partial(_tau_leap_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
            pl.BlockSpec((block_b, block_n), lambda i, j, k: (i, j)),
            pl.BlockSpec((block_b, block_n), lambda i, j, k: (i, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block_b, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Bp, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_b, block_n), jnp.int32)],
        interpret=interpret,
    )(s_i8p, jt_p, b_p, s_fp, u_p, scale.reshape(1), dt.reshape(1))
    return out[:B, :N]
