"""Pallas kernels for sparse (neighbor-list) Ising problems.

Two kernels over the padded `SparseIsing` layout (`repro.core.sparse`):

  sparse_fields        — local fields h = gather(s, nbr_idx) . nbr_w + b,
                         the O(n * max_deg) analogue of the dense int8
                         matmul engine.
  colored_gibbs_sweep  — one full chromatic Gibbs sweep fused over all
                         color phases, the arbitrary-graph generalization
                         of `lattice_gibbs.lattice_gibbs_sweep` (which is
                         the special case "king's lattice + 4-coloring +
                         stencil shifts instead of index gathers").

Layout: grid over batch blocks; each program holds a state block plus the
whole neighbor table in VMEM. A 3-regular n=4096 graph is 144 KiB of
tables, so the topology stays resident while the batch streams, matching
the weight-stationary story of the silicon.

The site axis is folded into (rows, 128) lane tiles, padded to whole
(8, 128) vregs, and each neighbor index is split into its row (idx // 128)
and lane (idx % 128). Mosaic gathers only within a 2D tile along one axis,
so the gather of slot k runs over the state's rows: row r of the state is
broadcast to every output row, each site takes its lane from it with
`jnp.take_along_axis`, and keeps the value where its neighbor lives in row
r. That is O(rows^2) vreg work per slot and chain, which is cheap up to a
few thousand rows of 128 sites; larger graphs need graph-partitioned blocks.
The gather is exact, and the slots are then added in `slot_sum`'s fixed
order, so the ref backend, the jnp oracle, and this kernel in interpret
mode agree bit-for-bit. Padded slots index the site itself with weight 0,
so no degree masking appears anywhere in the inner loop; padded sites of
the tiling point at site 0 with weight 0 and belong to no color.

`beta` rides along as an SMEM scalar (like the lattice sweep), so annealed
schedules drive the fused sweep without retracing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_VREG_SITES = 8 * _LANES  # the site axis is padded to whole (8, 128) vregs


def _tiled(x: jax.Array, n_pad: int, fill) -> jax.Array:
    """Pad the last (site) axis to n_pad and fold it into (n_pad/128, 128)."""
    pad = [(0, 0)] * (x.ndim - 1) + [(0, n_pad - x.shape[-1])]
    x = jnp.pad(x, pad, constant_values=fill)
    return x.reshape(x.shape[:-1] + (n_pad // _LANES, _LANES))


def _tiled_tables(nbr_idx, nbr_w, b, n_pad):
    """(md, R, 128) lane index, row index and weight per slot; (R, 128) bias."""
    idx = _tiled(nbr_idx.T, n_pad, 0)
    return idx % _LANES, idx // _LANES, _tiled(nbr_w.T, n_pad, 0), _tiled(b, n_pad, 0)


def _chain_fields(s_ref, chain, lo_ref, hi_ref, w_ref, b):
    """(R, 128) local fields of one chain of the block."""
    rows, lanes = b.shape
    h = None
    for k in range(lo_ref.shape[0]):
        lo, hi = lo_ref[k], hi_ref[k]

        def pick(r, g):
            src = jnp.broadcast_to(s_ref[chain, pl.ds(r, 1), :], (rows, lanes))
            return jnp.where(hi == r, jnp.take_along_axis(src, lo, axis=1), g)

        g = jax.lax.fori_loop(0, rows, pick, jnp.zeros((rows, lanes), s_ref.dtype))
        term = w_ref[k] * g  # slot by slot, in slot_sum's order
        h = term if h is None else h + term
    return h + b


def _fields_kernel(s_ref, lo_ref, hi_ref, w_ref, b_ref, out_ref):
    b = b_ref[...]

    def chain(i, carry):
        out_ref[i] = _chain_fields(s_ref, i, lo_ref, hi_ref, w_ref, b)
        return carry

    jax.lax.fori_loop(0, s_ref.shape[0], chain, 0)


def _sweep_kernel(
    s_ref, lo_ref, hi_ref, w_ref, b_ref, u_ref, masks_ref, beta_ref, out_ref
):
    # The sweep updates the state in place in out_ref: the gather reads the
    # state by row, so it has to live in a ref between color phases.
    out_ref[...] = s_ref[...]
    b = b_ref[...]            # (R, 128) f32
    beta = beta_ref[0]        # () f32 SMEM — inverse temperature
    for c in range(masks_ref.shape[0]):
        update = masks_ref[c] > 0.5  # (R, 128) independent-set mask

        def chain(i, carry):
            h = _chain_fields(out_ref, i, lo_ref, hi_ref, w_ref, b)
            # sigma(-2*(beta*h)): multiply order matches glauber.prob_up(beta*h)
            # so ref-backend trajectories reproduce bit-for-bit.
            p_up = jax.nn.sigmoid(-2.0 * (beta * h))
            proposal = jnp.where(u_ref[c, i] < p_up, 1.0, -1.0).astype(out_ref.dtype)
            out_ref[i] = jnp.where(update, proposal, out_ref[i])
            return carry

        jax.lax.fori_loop(0, out_ref.shape[0], chain, 0)


def _check_block_batch(name: str, B: int, bb: int) -> None:
    # ValueError, not assert: must fail fast with a readable message (and
    # survive `python -O`) instead of an opaque Pallas grid error.
    if B % bb != 0:
        raise ValueError(
            f"{name}: batch {B} is not divisible by block_batch {bb}; pass a "
            f"block_batch that divides the batch (or a batch that is a "
            f"multiple of block_batch)"
        )


def _padded_sites(n: int) -> int:
    return -(-n // _VREG_SITES) * _VREG_SITES


@functools.partial(jax.jit, static_argnames=("block_batch", "interpret"))
def sparse_fields(
    s: jax.Array,        # (B, n) f32 ±1
    nbr_idx: jax.Array,  # (n, max_deg) int32
    nbr_w: jax.Array,    # (n, max_deg) f32
    b: jax.Array,        # (n,) f32
    *,
    block_batch: int = 8,
    interpret: bool = True,
) -> jax.Array:
    B, n = s.shape
    bb = min(block_batch, B)
    _check_block_batch("sparse_fields", B, bb)
    n_pad = _padded_sites(n)
    lo, hi, w, b_t = _tiled_tables(nbr_idx, nbr_w, b, n_pad)
    md, R, L = lo.shape
    out = pl.pallas_call(
        _fields_kernel,
        grid=(B // bb,),
        in_specs=[
            pl.BlockSpec((bb, R, L), lambda i: (i, 0, 0)),
            pl.BlockSpec((md, R, L), lambda i: (0, 0, 0)),
            pl.BlockSpec((md, R, L), lambda i: (0, 0, 0)),
            pl.BlockSpec((md, R, L), lambda i: (0, 0, 0)),
            pl.BlockSpec((R, L), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bb, R, L), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, R, L), nbr_w.dtype),
        interpret=interpret,
    )(_tiled(s, n_pad, 1), lo, hi, w, b_t)
    return out.reshape(B, n_pad)[:, :n]


@functools.partial(jax.jit, static_argnames=("block_batch", "interpret"))
def colored_gibbs_sweep(
    s: jax.Array,          # (B, n) f32 ±1
    nbr_idx: jax.Array,    # (n, max_deg) int32
    nbr_w: jax.Array,      # (n, max_deg) f32
    b: jax.Array,          # (n,) f32
    uniforms: jax.Array,   # (C, B, n) f32 in [0,1)
    masks: jax.Array,      # (C, n) f32 {0,1} independent-set masks
    beta=None,             # () f32 inverse temperature (None -> 1.0)
    *,
    block_batch: int = 8,
    interpret: bool = True,
) -> jax.Array:
    B, n = s.shape
    bb = min(block_batch, B)
    _check_block_batch("colored_gibbs_sweep", B, bb)
    C = masks.shape[0]
    if beta is None:
        beta = jnp.ones((), jnp.float32)
    beta = jnp.asarray(beta, jnp.float32).reshape(1)
    n_pad = _padded_sites(n)
    lo, hi, w, b_t = _tiled_tables(nbr_idx, nbr_w, b, n_pad)
    md, R, L = lo.shape
    out = pl.pallas_call(
        _sweep_kernel,
        grid=(B // bb,),
        in_specs=[
            pl.BlockSpec((bb, R, L), lambda i: (i, 0, 0)),
            pl.BlockSpec((md, R, L), lambda i: (0, 0, 0)),
            pl.BlockSpec((md, R, L), lambda i: (0, 0, 0)),
            pl.BlockSpec((md, R, L), lambda i: (0, 0, 0)),
            pl.BlockSpec((R, L), lambda i: (0, 0)),
            pl.BlockSpec((C, bb, R, L), lambda i: (0, i, 0, 0)),
            pl.BlockSpec((C, R, L), lambda i: (0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bb, R, L), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, R, L), s.dtype),
        interpret=interpret,
    )(
        _tiled(s, n_pad, 1), lo, hi, w, b_t,
        _tiled(uniforms, n_pad, 1), _tiled(masks, n_pad, 0), beta,
    )
    return out.reshape(B, n_pad)[:, :n]
