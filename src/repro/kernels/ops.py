"""Jitted public wrappers for the Pallas kernels.

On a TPU backend the kernels run compiled (interpret=False); elsewhere they
run in interpret mode (bit-faithful Python execution of the kernel body) or
fall through to the jnp oracle for speed. `mode` overrides:

  'auto'      — TPU: compiled kernel; CPU/GPU: jnp reference (fast, exact)
  'kernel'    — force the Pallas kernel (interpret on non-TPU) — tests use this
  'reference' — force the jnp oracle
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import dense_field as _df
from repro.kernels import lattice_gibbs as _lg
from repro.kernels import ref as _ref
from repro.kernels import tau_leap as _tl


def on_tpu() -> bool:
    """The platform check every wrapper and the driver's "auto" share:
    kernels compile on a TPU and run in interpret mode elsewhere."""
    return jax.default_backend() == "tpu"


def lattice_gibbs_sweep(
    s, w, b, uniforms, colors, frozen, clamp_value, beta=None, mode: str = "auto", **kw
):
    if mode == "reference" or (mode == "auto" and not on_tpu()):
        cm = colors > 0.5
        fz = frozen > 0.5
        return _ref.lattice_gibbs_sweep_ref(s, w, b, uniforms, cm, fz, clamp_value, beta)
    # batch/block_batch divisibility is validated inside the kernel wrapper
    # (a readable ValueError at call/trace time, not a Pallas grid error)
    return _lg.lattice_gibbs_sweep(
        s, w, b, uniforms, colors, frozen, clamp_value, beta, interpret=not on_tpu(), **kw
    )


def sparse_fields(s, nbr_idx, nbr_w, b, mode: str = "auto", **kw):
    if mode == "reference" or (mode == "auto" and not on_tpu()):
        return _ref.sparse_fields_ref(s, nbr_idx, nbr_w, b)
    from repro.kernels import sparse_gather as _sg

    return _sg.sparse_fields(s, nbr_idx, nbr_w, b, interpret=not on_tpu(), **kw)


def colored_gibbs_sweep(s, nbr_idx, nbr_w, b, uniforms, masks, beta=None, mode: str = "auto", **kw):
    if mode == "reference" or (mode == "auto" and not on_tpu()):
        return _ref.colored_gibbs_sweep_ref(s, nbr_idx, nbr_w, b, uniforms, masks > 0.5, beta)
    from repro.kernels import sparse_gather as _sg

    # batch/block_batch divisibility is validated inside the kernel wrapper
    return _sg.colored_gibbs_sweep(
        s, nbr_idx, nbr_w, b, uniforms, masks, beta, interpret=not on_tpu(), **kw
    )


def dense_field(s_i8, j_i8, b, scale, mode: str = "auto", **kw):
    if mode == "reference" or (mode == "auto" and not on_tpu()):
        return _ref.dense_field_ref(s_i8, j_i8, b, scale)
    return _df.dense_field(s_i8, j_i8, b, scale, interpret=not on_tpu(), **kw)


def tau_leap_step(s, j_i8, b, scale, uniforms, dt, mode: str = "auto", **kw):
    """Dense tau-leap step; under `jax.vmap` the kernel's mapped calls become
    the rows of one call (`tau_leap.tau_leap_rows`)."""
    if mode == "reference" or (mode == "auto" and not on_tpu()):
        return _ref.tau_leap_step_ref(s, j_i8, b, scale, uniforms, dt)
    return _tl.tau_leap_rows(s, j_i8, b, scale, uniforms, dt, interpret=not on_tpu(), **kw)


def quantize_dense(J: jax.Array, bits: int = 8) -> tuple[jax.Array, jax.Array]:
    """Quantize a float coupling matrix to (int8 codes, f32 scale)."""
    qmax = float(2 ** (bits - 1) - 1)
    scale = jnp.max(jnp.abs(J)) / qmax
    scale = jnp.where(scale == 0, 1.0, scale)
    codes = jnp.clip(jnp.round(J / scale), -qmax, qmax).astype(jnp.int8)
    return codes, scale.astype(jnp.float32)


def flash_attention(q, k, v, causal=True, mode: str = "auto", **kw):
    """(BH, S, d) fused attention; oracle on CPU, Pallas kernel on TPU."""
    from repro.kernels import flash_attention as _fa

    if mode == "reference" or (mode == "auto" and not on_tpu()):
        return _ref.flash_attention_ref(q, k, v, causal=causal)
    return _fa.flash_attention(q, k, v, causal=causal, interpret=not on_tpu(), **kw)
