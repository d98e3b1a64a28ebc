"""Pallas TPU kernel: int8 binary dot-product engine (dense local fields).

The chip's synapse is an int8-weight x binary-activation multiply-accumulate.
On TPU the exact analogue is an int8 MXU matmul with int32 accumulation:
spins ±1 are exactly representable in int8, so h = (s @ J^T) * scale + b is
bit-exact w.r.t. the fixed-point silicon (no float rounding in the
accumulate). Used for dense problems (SK / MaxCut / decision models).

Blocked (BB x BK) @ (BK x BN) matmul, k-innermost grid, int32 VMEM scratch
accumulator, fused dequant+bias epilogue on the last k step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dense_field_kernel(s_ref, jt_ref, b_ref, scale_ref, out_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        s_ref[...],
        jt_ref[...],
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == nk - 1)
    def _epilogue():
        out_ref[...] = acc_ref[...].astype(jnp.float32) * scale_ref[0] + b_ref[...]


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    n = x.shape[axis]
    rem = (-n) % mult
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads)


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_n", "block_k", "interpret")
)
def dense_field(
    s_i8: jax.Array,   # (B, N) int8 in {-1,+1}
    j_i8: jax.Array,   # (N, N) int8 weight codes (symmetric)
    b: jax.Array,      # (N,) f32
    scale: jax.Array,  # () f32 dequantization scale
    *,
    block_b: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = True,
) -> jax.Array:
    B, N = s_i8.shape
    s_p = _pad_to(_pad_to(s_i8, 0, block_b), 1, block_k)
    jt_p = _pad_to(_pad_to(j_i8.T, 0, block_k), 1, block_n)
    b_p = _pad_to(b[None, :], 1, block_n)
    Bp, Kp = s_p.shape
    _, Np = jt_p.shape
    nk = Kp // block_k
    grid = (Bp // block_b, Np // block_n, nk)
    out = pl.pallas_call(
        functools.partial(_dense_field_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block_b, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Bp, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_b, block_n), jnp.int32)],
        interpret=interpret,
    )(s_p, jt_p, b_p, scale.reshape(1))
    return out[:B, :N]
