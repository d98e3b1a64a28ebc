"""Pallas TPU kernel: fused dense tau-leap PASS update step.

One asynchronous-model step for a dense problem, fully fused: int8 MXU
field matmul -> flip rates -> Bernoulli flips -> new state, with the spin
update applied in the matmul epilogue (fields never round-trip to HBM).
This is the throughput kernel for large SK/MaxCut sampling sweeps; the
chip analogue is "synapse + neuron + latch" operating concurrently.

Chains are the kernel's rows. `run()` steps each chain as one row
(`s[None]`) and maps chains with `jax.vmap`; `tau_leap_rows` turns that map
into one call whose rows are the chains: `n_chains` rows, padded to a
multiple of the MXU's 128-row tile, with J read once per tile and step.
`n_chains=1` is not mapped and keeps the one-row call. The rows of each
traced call are noted in `repro.core.tracing.row_occupancy`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import tracing
from repro.kernels.dense_field import _pad_to

ROW_TILE = 128  # the MXU's rows: the default block_b


def _tau_leap_kernel(
    s_mat_ref,   # (BB, BK) f32 — matmul operand (k-indexed block of spins)
    jt_ref,      # (BK, BN) int8
    b_ref,       # (1, BN) f32 shared bias, or (BB, BN) per-row biases
    s_out_ref,   # (BB, BN) f32 — current spins at the OUTPUT block
    u_ref,       # (BB, BN) f32 uniforms
    scale_ref,   # (1,) f32 SMEM shared scale, or (BB, 1) f32 per-row scales
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
        s_mat_ref[...].astype(jnp.int8),  # ±1: exact
        jt_ref[...],
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == nk - 1)
    def _epilogue():
        scale = scale_ref[0] if len(scale_ref.shape) == 1 else scale_ref[...]
        h = acc_ref[...].astype(jnp.float32) * scale + b_ref[...]
        s = s_out_ref[...]
        rate = jax.nn.sigmoid(2.0 * h * s)
        p_flip = 1.0 - jnp.exp(-dt_ref[0] * rate)
        out_ref[...] = jnp.where(u_ref[...] < p_flip, -s, s)


def _tile(n: int, most: int) -> int:
    """The widest multiple of 128, up to `most`, that divides `n` rounded up
    to a multiple of 128."""
    n128 = -(-n // 128) * 128
    return max(t for t in range(128, min(most, n128) + 1, 128) if n128 % t == 0)


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_n", "block_k", "interpret")
)
def tau_leap_step(
    s: jax.Array,        # (B, N) f32 ±1
    j_i8: jax.Array,     # (N, N) int8
    b: jax.Array,        # (N,) f32, or (B, N) per row
    scale: jax.Array,    # () f32, or (B,) per row
    uniforms: jax.Array, # (B, N) f32
    dt: jax.Array,       # () f32
    *,
    block_b: int = ROW_TILE,
    block_n: int | None = None,
    block_k: int | None = None,
    interpret: bool = True,
) -> jax.Array:
    """One tau-leap step of B rows in one `pallas_call`.

    Row r flips spin i where `uniforms[r, i] < 1 - exp(-dt * sigmoid(2 h s))`
    with `h = acc * scale + b` and `acc = s_r . J_i` (int8 in, int32 out).
    The rows are padded to a multiple of `block_b`, the MXU's row tile, so
    `B` rows fill `B / block_b` of it rounded up. `scale` and `b` are shared
    by all rows, or given per row (`(B,)`, `(B, N)`): the per-row forms are
    read from VMEM blocks and the epilogue does the same float operations in
    the same order, so a row's result does not depend on which form, or on
    which other rows share its call.

    Unless given, `block_k` spans up to 2048 sites of a row (all of N = 2048
    in one k step) and `block_n` up to 512, about 6 MiB of VMEM. A step of
    128 rows at N = 2048 took 14.0 µs so on a TPU v5e, 14.8 µs with 256-wide
    blocks and 77 µs with 128 x 128 ones, whose 256 grid programs cost more
    than reading J's 4 MiB. Each block casts its spins to int8 (exact: ±1).
    """
    B, N = s.shape
    block_n = block_n or _tile(N, 512)
    block_k = block_k or _tile(N, 2048)
    s_kp = _pad_to(_pad_to(s, 0, block_b), 1, block_k)
    s_fp = _pad_to(_pad_to(s, 0, block_b), 1, block_n)
    u_p = _pad_to(_pad_to(uniforms, 0, block_b), 1, block_n)
    jt_p = _pad_to(_pad_to(j_i8.T, 0, block_k), 1, block_n)
    if b.ndim == 1:
        b_p = _pad_to(b[None, :], 1, block_n)
        b_spec = pl.BlockSpec((1, block_n), lambda i, j, k: (0, j))
    else:
        b_p = _pad_to(_pad_to(b, 0, block_b), 1, block_n)
        b_spec = pl.BlockSpec((block_b, block_n), lambda i, j, k: (i, j))
    if scale.ndim == 0:
        scale_p = scale.reshape(1)
        scale_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    else:
        scale_p = _pad_to(scale.reshape(B, 1), 0, block_b)
        scale_spec = pl.BlockSpec((block_b, 1), lambda i, j, k: (i, 0))
    Bp, Kp = s_kp.shape
    _, Np = jt_p.shape
    nk = Kp // block_k
    grid = (Bp // block_b, Np // block_n, nk)
    out = pl.pallas_call(
        functools.partial(_tau_leap_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            b_spec,
            pl.BlockSpec((block_b, block_n), lambda i, j, k: (i, j)),
            pl.BlockSpec((block_b, block_n), lambda i, j, k: (i, j)),
            scale_spec,
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block_b, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Bp, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_b, block_n), jnp.int32)],
        interpret=interpret,
    )(s_kp, jt_p, b_p, s_fp, u_p, scale_p, dt.reshape(1))
    return out[:B, :N]


def _noted_step(s, j_i8, b, scale, uniforms, dt, *, calls, block_b=ROW_TILE, **kw):
    """`tau_leap_step`, noting its rows in `tracing` while it is traced."""
    rows = s.shape[0]
    tracing.note_rows("tau_leap_step", rows, -(-rows // block_b) * block_b, calls)
    return tau_leap_step(s, j_i8, b, scale, uniforms, dt, block_b=block_b, **kw)


def tau_leap_rows(s, j_i8, b, scale, uniforms, dt, **kw):
    """`tau_leap_step`, whose `jax.vmap` maps chains to the kernel's rows.

    Called on its own it is `tau_leap_step` (keyword arguments go to it).
    Under `jax.vmap` — `run()` maps each chain's scan over chains, and a
    chain steps as the one row `s[None]` — the A mapped calls of B rows
    become ONE call of A*B rows, so the MXU's row tile carries up to
    `block_b` chains and J is read once per row tile, not once per chain.
    A scale or bias that differs between chains (a per-chain schedule scales
    both, field noise shifts the bias) becomes a per-row operand; a shared
    one stays shared. Mapped couplings or `dt` (never in `run()`) fall back
    to vmap's default rule: one call per mapped element, as a grid axis.

    Each trace notes the kernel's rows per call, the padded rows the MXU
    processes, and the calls per step in `tracing.row_occupancy`.
    """

    @jax.custom_batching.custom_vmap
    def step(s, j_i8, b, scale, uniforms, dt):
        return _noted_step(s, j_i8, b, scale, uniforms, dt, calls=1, **kw)

    @step.def_vmap
    def as_rows(A, in_batched, s, j_i8, b, scale, uniforms, dt):
        s_m, j_m, b_m, scale_m, u_m, dt_m = in_batched
        if j_m or dt_m:
            call = functools.partial(_noted_step, calls=A, **kw)
            axes = [0 if m else None for m in in_batched]
            return jax.vmap(call, in_axes=axes)(s, j_i8, b, scale, uniforms, dt), True
        B, N = s.shape[-2:]
        rows = lambda x: jnp.broadcast_to(x, (A, B, N)).reshape(A * B, N)
        if scale_m or scale.ndim:  # per-row scales
            scale = scale.reshape(A, -1) if scale_m else scale
            scale = jnp.broadcast_to(scale, (A, B)).reshape(A * B)
        if b_m or b.ndim == 2:  # per-row biases
            b = rows(b.reshape(A, -1, N) if b_m else b)
        out = _noted_step(rows(s), j_i8, b, scale, rows(uniforms), dt, calls=1, **kw)
        return out.reshape(A, B, N), True

    return step(s, j_i8, b, scale, uniforms, dt)
