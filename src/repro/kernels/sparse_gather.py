"""Pallas kernels for sparse (neighbor-list) Ising problems.

`colored_gibbs_sweep` runs one full chromatic Gibbs sweep over the
padded `SparseIsing` layout (`repro.core.sparse`), fused over all color
phases: the arbitrary-graph generalization of
`lattice_gibbs.lattice_gibbs_sweep` (which is the special case "king's
lattice + 4-coloring + stencil shifts instead of index gathers").

Sweep layout: sites x chains. The state is held as (n_pad, L) float32,
one site per row and one chain per lane (L chains up to 128 per lane
block, a grid axis over lane blocks beyond that), so a neighbour's spins
in every chain at once are one row, read with one dynamic row load whose
index comes from SMEM. A sweep costs n * max_deg row loads for all chains
together. The walk is planned once per problem (`sweep_plan`): each color
class lists its sites in blocks of 8, with their neighbour rows (SMEM) and
weights (one (8, 128) tile per block, slot k in lane k). For a block the
kernel gathers the 8 neighbour rows of each slot into an (8, L) tile,
adds the slots in `slot_sum`'s order, adds the bias, computes
sigmoid(-2 * (beta * h)) and compares with each site's uniform, and
stores the 8 new rows back by index. Phase c reads every neighbour from
a copy of the state as the phase found it and writes its class into the
output, so the loads of one block never wait for the stores of the block
before; the copy catches up between phases. Padded slots index the site
itself with weight 0, and padding list entries point at a padding row of
the state, so no degree or class-size masking appears in the inner loop.
The v5e compiler schedules a block in about 80 bundles, bound by the
scalar slots (SMEM index loads and row addresses): about 31 µs a sweep of
n = 4096 at degree 3, for 64 or 128 chains (TPU v5e).

The fields are the same float32 operations in the same order as
`ref.colored_gibbs_sweep_ref` and the ref backend, and each site consumes
its own color's uniform, so the kernel is bit-identical to both.

Chains in lanes means a batch of B chains is one call. `colored_gibbs_lanes`
folds `run()`'s map over chains (`repro.kernels.chains`): `run()` sweeps
each chain as the one-chain batch `s[None]`, and the A mapped calls become
one call of A lanes. A shared inverse temperature rides in SMEM; a
per-chain one, a per-chain bias (field noise) or a per-chain update mask
(dropped sites) becomes a per-lane operand.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import chains

_LANES = 128  # the lanes of a vreg: chains per lane block
# Sites a step of a color's walk takes: one sublane tile of the state.
_BLOCK = 8
# Blocks per iteration of the walk's loop: the scheduler overlaps one
# block's field arithmetic with the next blocks' row loads.
_UNROLL = 4


class SweepPlan(NamedTuple):
    """A graph and its color classes laid out for `colored_gibbs_sweep`.

    The list holds each class's sites in site order, padded to whole blocks
    of 8 with the padding row `n_pad - 1` (no site, weight 0). Built by
    `sweep_plan` once per problem; `ColoredGibbs` keeps it in its state.
    """

    tiles: jax.Array   # (C + 1,) int32: class c walks blocks [tiles[c], tiles[c+1])
    sites: jax.Array   # (8 T,) int32: the state row of each list entry
    nbr: jax.Array     # (8 T * max_deg,) int32: each entry's neighbour rows, slot-minor
    w: jax.Array       # (8 T, 128) f32: each entry's slot weights in lanes
                       # 0..max_deg-1 and its bias in lane max_deg
    color: jax.Array   # (n,) int32: each site's class, C for none


def _state_rows(n: int) -> int:
    """Rows of the kernel's state: the sites and at least one padding row,
    rounded up to whole sublane tiles."""
    return -(-(n + 1) // _BLOCK) * _BLOCK


def sweep_plan(
    nbr_idx: jax.Array, nbr_w: jax.Array, b: jax.Array, masks: jax.Array
) -> SweepPlan:
    """The walk of `colored_gibbs_sweep` over the classes of `masks`.

    `masks` is (C, n) (nonzero = in the class); a site belongs to the first
    class that holds it, and a site in none is never updated. `b` is the
    shared bias, (n,); a per-chain one, (B, n), is not part of the plan.
    One stable sort of the sites by class, so a caller that sweeps one
    problem many times builds it once.
    """
    n, deg = nbr_idx.shape
    C = masks.shape[0]
    dummy = _state_rows(n) - 1
    member = masks != 0
    color = jnp.where(member.any(0), jnp.argmax(member, axis=0), C).astype(jnp.int32)
    by_class = jnp.argsort(color, stable=True).astype(jnp.int32)
    counts = jnp.sum(color[None, :] == jnp.arange(C)[:, None], axis=1).astype(jnp.int32)
    tiles = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(-(-counts // _BLOCK))])
    first = jnp.cumsum(counts) - counts  # rank in `by_class` of each class's first site
    p = jnp.arange(_BLOCK * (-(-n // _BLOCK) + C), dtype=jnp.int32)
    cls = jnp.sum(p[:, None] >= _BLOCK * tiles[None, 1:], axis=1)
    c = jnp.minimum(cls, C - 1)
    rank = p - _BLOCK * tiles[c]
    real = (cls < C) & (rank < counts[c])
    site = jnp.where(real, by_class[jnp.clip(first[c] + rank, 0, n - 1)], dummy)
    row = jnp.minimum(site, n - 1)
    nbr = jnp.where(real[:, None], nbr_idx[row], dummy)
    b = jnp.zeros((n,), jnp.float32) if b.ndim == 2 else b
    w = jnp.concatenate([nbr_w[row], b[row][:, None]], axis=1)
    w = jnp.where(real[:, None], w, 0.0).astype(jnp.float32)
    w = jnp.pad(w, ((0, 0), (0, _LANES - deg - 1)))
    return SweepPlan(tiles.astype(jnp.int32), site, nbr.reshape(-1).astype(jnp.int32), w, color)


def _sweep_kernel(*refs, deg, lane_b, lane_beta, lane_keep):
    tiles_ref, sites_ref, nbr_ref, beta_ref, w_ref, s_ref, u_ref = refs[:7]
    b_ref = refs[7] if lane_b else None
    keep_ref = refs[7 + lane_b] if lane_keep else None
    out_ref, read_ref = refs[-2:]
    beta = beta_ref[...] if lane_beta else beta_ref[0]  # (1, L) VMEM or () SMEM

    def rows(ref, idx):
        """The (8, L) tile of rows `idx` of `ref`, one dynamic load each."""
        return jnp.concatenate([ref[pl.ds(i, 1), :] for i in idx], axis=0)

    def block(t):
        p0 = pl.multiple_of(t * _BLOCK, _BLOCK)
        w = w_ref[pl.ds(p0, _BLOCK), :]                    # (8, 128) weights, bias
        sites = [sites_ref[p0 + r] for r in range(_BLOCK)]
        q0 = t * (_BLOCK * deg)
        h = None
        for k in range(deg):  # slot by slot, in slot_sum's order
            g = rows(read_ref, [nbr_ref[q0 + (r * deg + k)] for r in range(_BLOCK)])
            term = jnp.broadcast_to(w[:, k:k + 1], g.shape) * g
            h = term if h is None else h + term
        bias = rows(b_ref, sites) if lane_b else jnp.broadcast_to(w[:, deg:deg + 1], h.shape)
        h = h + bias
        # sigma(-2*(beta*h)): multiply order matches glauber.prob_up(beta*h)
        # so ref-backend trajectories reproduce bit-for-bit.
        p_up = jax.nn.sigmoid(-2.0 * (beta * h))
        new = jnp.where(rows(u_ref, sites) < p_up, 1.0, -1.0).astype(out_ref.dtype)
        if lane_keep:
            new = jnp.where(rows(keep_ref, sites) > 0.5, new, rows(read_ref, sites))
        for r, i in enumerate(sites):
            out_ref[pl.ds(i, 1), :] = new[r:r + 1]

    def phase(c, carry):
        # Phase c reads the state as it stood when the phase began (read_ref)
        # and writes its class into out_ref, so that no load waits for a
        # store; read_ref then catches up.
        @pl.when(c > 0)
        def _():
            read_ref[...] = out_ref[...]

        lo, hi = tiles_ref[c], tiles_ref[c + 1]

        def step(j, carry):
            # The last iteration may redo the class's last block: it reads
            # only read_ref, so it writes the same rows again.
            for q in range(_UNROLL):
                block(jnp.minimum(lo + j * _UNROLL + q, hi - 1))
            return carry

        return jax.lax.fori_loop(0, (hi - lo + _UNROLL - 1) // _UNROLL, step, carry)

    out_ref[...] = s_ref[...]
    read_ref[...] = s_ref[...]
    jax.lax.fori_loop(0, tiles_ref.shape[0] - 1, phase, 0)


def _lanes(x: jax.Array, n_pad: int, l_pad: int, fill) -> jax.Array:
    """(B, n) -> (n_pad, l_pad): sites in rows, chains in lanes, padded."""
    B, n = x.shape
    return jnp.pad(x.T, ((0, n_pad - n), (0, l_pad - B)), constant_values=fill)


def _own_color(x: jax.Array, color: jax.Array) -> jax.Array:
    """x[color[i], ..., i] for (C, ..., n) x: each site's entry of its class."""
    out = x[0]
    for c in range(1, x.shape[0]):
        out = jnp.where(color == c, x[c], out)
    return out


@functools.partial(jax.jit, static_argnames=("block_lanes", "interpret"))
def colored_gibbs_sweep(
    s: jax.Array,          # (B, n) f32 ±1
    nbr_idx: jax.Array,    # (n, max_deg) int32
    nbr_w: jax.Array,      # (n, max_deg) f32
    b: jax.Array,          # (n,) f32, or (B, n) per chain
    uniforms: jax.Array,   # (C, B, n) f32 in [0,1)
    masks: jax.Array,      # (C, n) f32 {0,1} independent-set masks, or (C, B, n) per chain
    beta=None,             # () f32 inverse temperature (None -> 1.0), or (B,) per chain
    plan: Optional[SweepPlan] = None,
    *,
    block_lanes: int = _LANES,
    interpret: bool = True,
) -> jax.Array:
    """One chromatic sweep of B chains in one `pallas_call`.

    In phase c every site of class c takes +1 where `uniforms[c]` lies below
    sigmoid(-2 beta h), as `ref.colored_gibbs_sweep_ref`. The classes must
    be disjoint (an independent-set partition, or part of one). Chains go
    in the lanes: up to `block_lanes` (a multiple of 128) per program, a
    grid axis over lane blocks beyond that.

    `plan` is `sweep_plan(nbr_idx, nbr_w, b, masks)` when given; without
    it the call builds one. Per-chain masks `(C, B, n)` are update masks
    within the plan's classes (a plan built from their union over chains
    when none is given); a per-chain bias and beta are read per lane.
    """
    if block_lanes <= 0 or block_lanes % _LANES:
        raise ValueError(
            f"colored_gibbs_sweep: block_lanes {block_lanes} is not a positive "
            f"multiple of {_LANES}, the lanes of a vreg: chains go in the lanes"
        )
    B, n = s.shape
    deg = nbr_idx.shape[1]
    lane_keep = masks.ndim == 3
    if plan is None:
        plan = sweep_plan(nbr_idx, nbr_w, b, masks.any(1) if lane_keep else masks)
    if beta is None:
        beta = jnp.ones((), jnp.float32)
    beta = jnp.asarray(beta, jnp.float32)
    lane_b, lane_beta = b.ndim == 2, beta.ndim == 1
    n_pad = _state_rows(n)
    L = B if B <= block_lanes else block_lanes
    l_pad = -(-B // L) * L
    rows = lambda x, fill=0.0: _lanes(jnp.broadcast_to(x, (B, n)), n_pad, l_pad, fill)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    # One buffer per block: a program's state, uniforms and tables fill
    # VMEM once (about 2 MiB each at n = 4096), not twice.
    one = pl.Buffered(1)
    lane_block = pl.BlockSpec((n_pad, L), lambda i: (0, i), pipeline_mode=one)
    operands = [plan.tiles, plan.sites, plan.nbr]
    specs = [smem, smem, smem]
    if lane_beta:
        operands.append(jnp.pad(beta[None, :], ((0, 0), (0, l_pad - B))))
        specs.append(pl.BlockSpec((1, L), lambda i: (0, i), pipeline_mode=one))
    else:
        operands.append(beta.reshape(1))
        specs.append(smem)
    operands += [plan.w, rows(s, 1.0), rows(_own_color(uniforms, plan.color))]
    specs += [pl.BlockSpec(plan.w.shape, lambda i: (0, 0), pipeline_mode=one), lane_block, lane_block]
    if lane_b:
        operands.append(rows(b))
        specs.append(lane_block)
    if lane_keep:
        operands.append(rows(_own_color(masks.astype(jnp.float32), plan.color)))
        specs.append(lane_block)
    out = pl.pallas_call(
        functools.partial(
            _sweep_kernel, deg=deg, lane_b=lane_b, lane_beta=lane_beta, lane_keep=lane_keep,
        ),
        grid=(l_pad // L,),
        in_specs=specs,
        out_specs=lane_block,
        out_shape=jax.ShapeDtypeStruct((n_pad, l_pad), s.dtype),
        scratch_shapes=[pltpu.VMEM((n_pad, L), s.dtype)],
        interpret=interpret,
    )(*operands)
    return out[:n, :B].T


def _lanes_of(A, mapped, s, nbr_idx, nbr_w, b, uniforms, masks, beta, plan):
    """The A mapped calls of B chains as one call of A*B lanes. A bias, mask
    or beta that differs between chains (field noise, dropped sites, a
    per-chain schedule) becomes a per-chain operand; a shared one stays
    shared. The transposes to and from the kernel's sites x chains layout
    happen inside the call."""
    s_m, _, _, b_m, u_m, masks_m, beta_m, _ = mapped
    B, n = s.shape[-2:]
    C = uniforms.shape[-3]
    s = chains.fold_operand(s, s_m, A, (B, n))
    u = chains.fold_operand(uniforms, u_m, A, (C, B, n), axis=1)
    if b_m or b.ndim == 2:  # per-chain biases
        b = chains.fold_operand(b, b_m, A, (B, n))
    if masks_m or masks.ndim == 3:  # per-chain update masks
        masks = chains.fold_operand(masks, masks_m, A, (C, B, n), axis=1)
    if beta_m or beta.ndim:  # per-chain inverse temperatures
        beta = chains.fold_operand(beta, beta_m, A, (B,))
    return s, nbr_idx, nbr_w, b, u, masks, beta, plan


# `colored_gibbs_sweep` with `run()`'s chains in its lanes
# (`repro.kernels.chains`); mapped tables or plan take one call per mapped
# element.
colored_gibbs_lanes = chains.batched(
    colored_gibbs_sweep, "colored_gibbs_sweep", tile=_LANES, shared=(1, 2, 7), fold=_lanes_of
)
