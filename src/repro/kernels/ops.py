"""The sampler's one door to the Pallas kernels.

`repro.core.sampler_api` reaches `repro.kernels` through this module and
looks each kernel up on it while tracing. Every call runs the Pallas
kernel: compiled on a TPU, elsewhere in interpret mode, which executes
the kernel body faithfully on the host. Which path a sampler takes is
`run(backend=)`'s choice alone; the jnp oracles are `repro.kernels.ref`.
Under `jax.vmap` the kernels take `run()`'s chains by the rule of
`repro.kernels.chains`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import lattice_gibbs as _lg
from repro.kernels import sparse_gather as _sg
from repro.kernels import tau_leap as _tl

# The walk of `colored_gibbs_sweep` over a problem's color classes.
sweep_plan = _sg.sweep_plan


def on_tpu() -> bool:
    """The one platform check: kernels compile on a TPU and run in
    interpret mode elsewhere."""
    return jax.default_backend() == "tpu"


def _beta(beta) -> jax.Array:
    return jnp.ones((), jnp.float32) if beta is None else jnp.asarray(beta, jnp.float32)


def lattice_gibbs_sweep(s, w, b, uniforms, colors, frozen, clamp_value, beta=None, **kw):
    """Fused 4-color lattice sweep, tiled by rows (`lattice_gibbs`); one
    call per mapped chain."""
    return _lg.lattice_gibbs_rows(
        s, w, b, uniforms, colors, frozen, clamp_value, _beta(beta), interpret=not on_tpu(), **kw
    )


def colored_gibbs_sweep(s, nbr_idx, nbr_w, b, uniforms, masks, beta=None, plan=None, **kw):
    """Fused chromatic sweep of a sparse graph, chains in the kernel's lanes
    (`sparse_gather`). `plan`, when given, is `sweep_plan(nbr_idx, nbr_w,
    b, masks)`, built once per problem."""
    return _sg.colored_gibbs_lanes(
        s, nbr_idx, nbr_w, b, uniforms, masks, _beta(beta), plan, interpret=not on_tpu(), **kw
    )


def tau_leap_step(s, j_i8, b, scale, uniforms, dt, **kw):
    """Dense tau-leap step, chains in the kernel's rows (`tau_leap`)."""
    return _tl.tau_leap_rows(s, j_i8, b, scale, uniforms, dt, interpret=not on_tpu(), **kw)


def quantize_dense(J: jax.Array, bits: int = 8) -> tuple[jax.Array, jax.Array]:
    """Quantize a float coupling matrix to (int8 codes, f32 scale)."""
    qmax = float(2 ** (bits - 1) - 1)
    scale = jnp.max(jnp.abs(J)) / qmax
    scale = jnp.where(scale == 0, 1.0, scale)
    codes = jnp.clip(jnp.round(J / scale), -qmax, qmax).astype(jnp.int8)
    return codes, scale.astype(jnp.float32)
