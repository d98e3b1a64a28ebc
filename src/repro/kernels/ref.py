"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth for tests (interpret-mode allclose sweeps) and
the CPU fallback used by ops.py when no TPU is present.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.ising import KING_OFFSETS, shift2d
from repro.core.sparse import slot_sum


def lattice_fields_ref(s: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """King's-move local fields. s: (B,H,W) ±1; w: (8,H,W); b: (H,W)."""
    acc = jnp.zeros_like(s)
    for k, (dy, dx) in enumerate(KING_OFFSETS):
        acc = acc + w[k] * shift2d(s, dy, dx)
    return acc + b


def lattice_gibbs_sweep_ref(
    s: jax.Array,
    w: jax.Array,
    b: jax.Array,
    uniforms: jax.Array,
    color_masks: jax.Array,
    frozen: jax.Array,
    clamp_value: jax.Array,
    beta=None,
) -> jax.Array:
    """One full 4-color chromatic Gibbs sweep at inverse temperature beta.

    s: (B,H,W) ±1; uniforms: (4,B,H,W); color_masks: (4,H,W) bool;
    frozen: (H,W) bool; clamp_value: (H,W) ±1 (applied where frozen);
    beta: () scalar (None -> 1.0).
    """
    if beta is None:
        beta = jnp.ones((), jnp.float32)
    beta = jnp.asarray(beta, jnp.float32)
    for c in range(color_masks.shape[0]):
        h = lattice_fields_ref(s, w, b)
        # multiply order matches glauber.prob_up(beta*h): sigma(-2*(beta*h))
        p_up = jax.nn.sigmoid(-2.0 * (beta * h))
        proposal = jnp.where(uniforms[c] < p_up, 1.0, -1.0).astype(s.dtype)
        upd = color_masks[c][None] & (~frozen)[None]
        s = jnp.where(upd, proposal, s)
    s = jnp.where(frozen[None], clamp_value[None].astype(s.dtype), s)
    return s


def sparse_fields_ref(
    s: jax.Array, nbr_idx: jax.Array, nbr_w: jax.Array, b: jax.Array
) -> jax.Array:
    """Padded neighbor-list local fields. s: (B,n) ±1; nbr_idx/nbr_w:
    (n,max_deg); b: (n,). Padded slots index the site itself with weight 0.
    The slots are added in `slot_sum`'s order, as `SparseIsing.neighbor_sum`
    and the Pallas kernel add them — bit-parity by construction."""
    gathered = jnp.take(s, nbr_idx, axis=-1)  # (B, n, max_deg)
    return slot_sum(nbr_w, gathered) + b


def colored_gibbs_sweep_ref(
    s: jax.Array,
    nbr_idx: jax.Array,
    nbr_w: jax.Array,
    b: jax.Array,
    uniforms: jax.Array,
    color_masks: jax.Array,
    beta=None,
) -> jax.Array:
    """One full chromatic Gibbs sweep on a sparse graph at inverse
    temperature beta.

    s: (B,n) ±1; uniforms: (C,B,n); color_masks: (C,n) bool independent-set
    masks; beta: () scalar (None -> 1.0).
    """
    if beta is None:
        beta = jnp.ones((), jnp.float32)
    beta = jnp.asarray(beta, jnp.float32)
    for c in range(color_masks.shape[0]):
        h = sparse_fields_ref(s, nbr_idx, nbr_w, b)
        # multiply order matches glauber.prob_up(beta*h): sigma(-2*(beta*h))
        p_up = jax.nn.sigmoid(-2.0 * (beta * h))
        proposal = jnp.where(uniforms[c] < p_up, 1.0, -1.0).astype(s.dtype)
        s = jnp.where(color_masks[c][None], proposal, s)
    return s


def dense_field_ref(s_i8: jax.Array, j_i8: jax.Array, b: jax.Array, scale: jax.Array) -> jax.Array:
    """int8 binary dot-product engine: h = (s @ J^T) * scale + b.

    s_i8: (B,N) int8 in {-1,+1}; j_i8: (N,N) int8 weight codes;
    scale: () f32 dequant scale, or (B,) one per row; b: (N,) f32, or
    (B,N) one per row. Returns (B,N) f32.
    """
    acc = jnp.dot(
        s_i8.astype(jnp.int32), j_i8.astype(jnp.int32).T, preferred_element_type=jnp.int32
    )
    return acc.astype(jnp.float32) * jnp.reshape(scale, (-1, 1)) + b


def tau_leap_step_ref(
    s: jax.Array,
    j_i8: jax.Array,
    b: jax.Array,
    scale: jax.Array,
    uniforms: jax.Array,
    dt: jax.Array,
) -> jax.Array:
    """Fused dense tau-leap PASS update.

    s: (B,N) f32 ±1. Flip each spin w.p. 1-exp(-dt*sigma(2 h s)). scale
    and b are shared, or given per row (see `dense_field_ref`).
    """
    h = dense_field_ref(s.astype(jnp.int8), j_i8, b, scale)
    rate = jax.nn.sigmoid(2.0 * h * s)
    p_flip = 1.0 - jnp.exp(-dt * rate)
    return jnp.where(uniforms < p_flip, -s, s)


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True) -> jax.Array:
    """Oracle for kernels.flash_attention. q/k/v: (BH, S, d)."""
    d = q.shape[-1]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s / jnp.sqrt(jnp.asarray(d, jnp.float32))
    if causal:
        Sq, Sk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(Sk)[None, :] <= jnp.arange(Sq)[:, None]
        s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)
