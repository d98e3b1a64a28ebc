"""Pallas TPU kernel: fused chromatic Gibbs sweep on the king's-move lattice.

This is the TPU realization of the PASS chip's per-neuron pipeline — binary
dot-product (8-neighbor stencil, weight-stationary), sigmoid activation,
stochastic compare, output latch — fused over a full 4-color sweep.

Layout: the lattice is tiled by rows. The grid is (row tiles, batch
blocks); each program holds a (BB, TH, W) state tile with the same TH rows
of the weight planes, bias, uniforms, update masks, frozen mask and clamp
values, so VMEM per program is bounded by the tile and not by H. W stays
whole, in the lane dimension. The batch blocks are the inner grid axis, so
a tile's weight planes and masks stay in VMEM while its chains go by.

Halos. Color = (y % 2) * 2 + x % 2 and tiles start on an even row, so a
tile's even rows update in phases 0-1 and its odd rows in phases 2-3. Its
first row then needs the row above at its old value, and its last (odd)
row needs the row below after phases 0-1 — which needs the row below that
at its old value. Each program reads the 8-row (sublane-aligned) edge
blocks of the neighbouring tiles as extra inputs, recomputes the even row
below it in phases 0-1 from that row's own weights, bias and uniforms, and
writes only its own rows. Beyond the lattice, spins are zero, as without
tiles, so every site gets the same operations in the same order as the
untiled sweep and `ref.lattice_gibbs_sweep_ref`: results are bit-identical
at every tile height.

The stencil is computed with explicit pad+slice shifts (no gather), which
maps to cheap VPU vector shifts on TPU.

The inverse temperature `beta` rides along as an SMEM scalar (like `dt` in
the tau-leap kernel), so annealed schedules drive the fused sweep without
retracing: p_up = sigma(-2*beta*h).

`run()` sweeps each chain as the one-chain batch `s[None]` and maps chains
with `jax.vmap`; `lattice_gibbs_rows` maps them as vmap's default rule
does, one call per chain as a grid axis (`repro.kernels.chains`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.ising import KING_OFFSETS, N_KING_COLORS
from repro.kernels import chains

# Rows of a halo block: the sublane tile of 32-bit values. Tile heights are
# multiples of it, so every block starts on an aligned row.
EDGE = 8
# VMEM a program's tile may take, in bytes: its double-buffered blocks plus
# the sweep's working copies (see `_bytes_per_row`), under the 16 MiB that
# Mosaic allows a kernel by default. At W = 384 and one chain per block
# this gives 64-row tiles.
VMEM_BUDGET = 8 * 2**20


def _shift(x: jax.Array, dy: int, dx: int) -> jax.Array:
    """out[..., y, x] = x[..., y+dy, x+dx], zero padded (pad+slice form)."""
    H, W = x.shape[-2], x.shape[-1]
    pad = [(0, 0)] * (x.ndim - 2) + [(1, 1), (1, 1)]
    p = jnp.pad(x, pad)
    return jax.lax.slice_in_dim(
        jax.lax.slice_in_dim(p, 1 + dy, 1 + dy + H, axis=x.ndim - 2),
        1 + dx,
        1 + dx + W,
        axis=x.ndim - 1,
    )


def _fields(s: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    acc = jnp.zeros_like(s)
    for k, (dy, dx) in enumerate(KING_OFFSETS):
        acc = acc + w[k] * _shift(s, dy, dx)
    return acc + b


def _rows(top, tile, bottom):
    """The window of rows [r0 - 1, r0 + TH + 2): one row above the tile, the
    tile, and two below it (row axis second to last)."""
    return jnp.concatenate([top, tile, bottom], axis=-2)


def _sweep_kernel(
    s_ref, s_up_ref, s_dn_ref, w_ref, w_dn_ref, b_ref, b_dn_ref, u_ref, u_dn_ref,
    colors_ref, colors_dn_ref, frozen_ref, frozen_dn_ref, clampv_ref, beta_ref, out_ref,
):
    # Edge blocks beyond the lattice (the first tile's row above, the last
    # tile's rows below) are read clamped to a block that is there and are
    # zeroed here.
    first = pl.program_id(0) == 0
    last = pl.program_id(0) == pl.num_programs(0) - 1
    s_tile = s_ref[...]                                   # (BB, TH, W) f32 ±1
    bb, W = s_tile.shape[0], s_tile.shape[-1]
    zero_row = jnp.zeros((1, W), s_tile.dtype)
    top = jnp.where(first, 0.0, s_up_ref[:, EDGE - 1:EDGE, :]).astype(s_tile.dtype)
    below = jnp.where(last, 0.0, s_dn_ref[:, 0:2, :]).astype(s_tile.dtype)
    s = _rows(top, s_tile, below)                         # (BB, TH + 3, W)
    # The rows outside the tile are never written: the row above and the
    # second row below have zero update masks; the row below updates in
    # phases 0-1 only, as in its own tile.
    w = _rows(jnp.zeros((8, 1, W), w_ref.dtype), w_ref[...], w_dn_ref[:, 0:2, :])
    b = _rows(zero_row, b_ref[...], b_dn_ref[0:2, :])
    frozen = _rows(zero_row, frozen_ref[...], frozen_dn_ref[0:2, :])
    beta = beta_ref[0]                                    # () f32 SMEM
    free = 1.0 - frozen
    zero_mask = jnp.zeros((1, W), colors_ref.dtype)
    for c in range(N_KING_COLORS):
        if c < 2:
            u_below = u_dn_ref[c][:, 0:2, :]
            mask_below = jnp.where(last, 0.0, colors_dn_ref[c][0:1, :]).astype(zero_mask.dtype)
        else:
            u_below = jnp.zeros((bb, 2, W), u_ref.dtype)
            mask_below = zero_mask
        u = _rows(jnp.zeros((bb, 1, W), u_ref.dtype), u_ref[c], u_below)
        mask_c = _rows(zero_mask, colors_ref[c], jnp.concatenate([mask_below, zero_mask]))
        h = _fields(s, w, b[None])
        # sigma(-2*(beta*h)): multiply order matches glauber.prob_up(beta*h)
        # so ref-backend trajectories reproduce bit-for-bit.
        p_up = jax.nn.sigmoid(-2.0 * (beta * h))
        proposal = jnp.where(u < p_up, 1.0, -1.0).astype(s.dtype)
        upd = (mask_c * free)[None] > 0.5
        s = jnp.where(upd, proposal, s)
    clamped = frozen_ref[...][None] > 0.5
    out_ref[...] = jnp.where(clamped, clampv_ref[...][None], s[:, 1:-2, :])


def _bytes_per_row(W: int, bb: int, itemsize: int) -> int:
    """VMEM one tile row takes in a program of `bb` chains: each block
    (per row: state, output and 4 uniforms per chain; 8 weights, 4 update
    masks, bias, frozen mask and clamp values) twice, for the pipeline's
    double buffer, and once more for the sweep's windows of them, plus
    about 8 state-sized temporaries per chain for the stencil."""
    blocks = 6 * bb + 15
    return W * itemsize * (3 * blocks + 8 * bb)


def tile_rows(H: int, W: int, bb: int, itemsize: int = 4) -> int:
    """The tallest tile, a multiple of `EDGE` rows that divides H rounded up
    to a multiple of `EDGE`, whose rows fit `VMEM_BUDGET`."""
    h8 = -(-H // EDGE) * EDGE
    most = max(EDGE, VMEM_BUDGET // _bytes_per_row(W, bb, itemsize))
    return max(t for t in range(EDGE, min(most, h8) + 1, EDGE) if h8 % t == 0)


def _pad_rows(x: jax.Array, rows: int) -> jax.Array:
    """`x` with zero rows appended on its row axis (second to last) up to `rows`."""
    pad = [(0, 0)] * x.ndim
    pad[-2] = (0, rows - x.shape[-2])
    return jnp.pad(x, pad)


@functools.partial(jax.jit, static_argnames=("block_batch", "block_rows", "interpret"))
def lattice_gibbs_sweep(
    s: jax.Array,          # (B, H, W) f32 ±1
    w: jax.Array,          # (8, H, W) f32
    b: jax.Array,          # (H, W) f32
    uniforms: jax.Array,   # (4, B, H, W) f32 in [0,1)
    colors: jax.Array,     # (4, H, W) f32 {0,1} update mask of each phase:
                           # within king color c (`king_color_masks`)
    frozen: jax.Array,     # (H, W) f32 {0,1}
    clamp_value: jax.Array,  # (H, W) f32 ±1
    beta=None,             # () f32 inverse temperature (None -> 1.0)
    *,
    block_batch: int = 8,
    block_rows: int | None = None,
    interpret: bool = True,
) -> jax.Array:
    """One 4-color sweep of B chains, tiled by `block_rows` rows (a multiple
    of 8; by default `tile_rows`). H is padded with zero rows that never
    update to a multiple of the tile, so the last tile may be short."""
    B, H, W = s.shape
    bb = min(block_batch, B)
    # ValueError, not assert: must fail fast with a readable message (and
    # survive `python -O`) instead of an opaque Pallas grid error.
    if B % bb != 0:
        raise ValueError(
            f"lattice_gibbs_sweep: batch {B} is not divisible by "
            f"block_batch {bb}; pass a block_batch that divides the batch "
            f"(or a batch that is a multiple of block_batch)"
        )
    th = block_rows or tile_rows(H, W, bb, s.dtype.itemsize)
    if th % EDGE:
        raise ValueError(f"lattice_gibbs_sweep: block_rows {th} is not a multiple of {EDGE}")
    if beta is None:
        beta = jnp.ones((), jnp.float32)
    beta = jnp.asarray(beta, jnp.float32).reshape(1)
    Hp = -(-H // th) * th
    s, w, b, uniforms, colors, frozen, clamp_value = (
        _pad_rows(x, Hp) for x in (s, w, b, uniforms, colors, frozen, clamp_value)
    )
    per_tile, edges = th // EDGE, Hp // EDGE
    up = lambda t: jnp.maximum(t * per_tile - 1, 0)      # edge block above tile t
    dn = lambda t: jnp.minimum((t + 1) * per_tile, edges - 1)  # edge block below it
    out = pl.pallas_call(
        _sweep_kernel,
        grid=(Hp // th, B // bb),
        in_specs=[
            pl.BlockSpec((bb, th, W), lambda t, i: (i, t, 0)),
            pl.BlockSpec((bb, EDGE, W), lambda t, i: (i, up(t), 0)),
            pl.BlockSpec((bb, EDGE, W), lambda t, i: (i, dn(t), 0)),
            pl.BlockSpec((8, th, W), lambda t, i: (0, t, 0)),
            pl.BlockSpec((8, EDGE, W), lambda t, i: (0, dn(t), 0)),
            pl.BlockSpec((th, W), lambda t, i: (t, 0)),
            pl.BlockSpec((EDGE, W), lambda t, i: (dn(t), 0)),
            pl.BlockSpec((N_KING_COLORS, bb, th, W), lambda t, i: (0, i, t, 0)),
            pl.BlockSpec((2, bb, EDGE, W), lambda t, i: (0, i, dn(t), 0)),
            pl.BlockSpec((N_KING_COLORS, th, W), lambda t, i: (0, t, 0)),
            pl.BlockSpec((2, EDGE, W), lambda t, i: (0, dn(t), 0)),
            pl.BlockSpec((th, W), lambda t, i: (t, 0)),
            pl.BlockSpec((EDGE, W), lambda t, i: (dn(t), 0)),
            pl.BlockSpec((th, W), lambda t, i: (t, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bb, th, W), lambda t, i: (i, t, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hp, W), s.dtype),
        interpret=interpret,
    )(s, s, s, w, w, b, b, uniforms, uniforms, colors, colors, frozen, frozen,
      clamp_value, beta)
    return out[:, :H]


# `lattice_gibbs_sweep` with `run()`'s chains mapped as vmap's default rule
# maps them: one one-chain call per chain, as a grid axis. It has no fold
# yet; the chains rule (`repro.kernels.chains`) notes its calls.
lattice_gibbs_rows = chains.batched(lattice_gibbs_sweep, "lattice_gibbs_sweep")
