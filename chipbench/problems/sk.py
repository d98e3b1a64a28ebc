"""Sherrington-Kirkpatrick spin glass: the yardstick's instance and energy.

J_ij ~ N(0, 1/n) for i < j, symmetric, zero diagonal, zero bias, made on
the device in one jitted call from a seed. The energy follows the
program's documented convention, E(s) = 1/2 s.J.s + b.s, and is written
here from that formula alone: nothing in this file but `program_problem`
touches the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def make(cfg: dict, seed: int) -> dict:
    """The instance of `cfg` (its size `n`) drawn from `seed`, on the device."""
    n = int(cfg["n"])

    @jax.jit
    def draw(k):
        a = jax.random.normal(k, (n, n), jnp.float32) / jnp.sqrt(jnp.float32(n))
        upper = jnp.triu(a, 1)
        return upper + upper.T

    return {"n": n, "J": draw(jax.random.key(seed)), "b": jnp.zeros((n,), jnp.float32)}


def program_problem(inst: dict):
    """The same instance as the program's `DenseIsing`."""
    from repro.core.ising import DenseIsing

    return DenseIsing(J=inst["J"], b=inst["b"])


def energy(inst: dict, s: jax.Array, prec: str) -> jax.Array:
    """(R,) energies of the (R, n) ±1 states `s`.

    "full" is float32 with every matmul pass (`Precision.HIGHEST`), so it
    is float32 on the chip too; "control" is bfloat16 throughout, the
    nearest precision below, each result rounded to it.
    """
    if prec == "full":
        js = jnp.dot(s, inst["J"], precision=HIGHEST)
        return 0.5 * jnp.sum(s * js, axis=-1) + jnp.dot(s, inst["b"], precision=HIGHEST)
    bf = jnp.bfloat16
    sb = s.astype(bf)
    js = rounded(jnp.dot(sb, inst["J"].astype(bf), preferred_element_type=bf))
    pair = rounded(jnp.sum(sb * js, axis=-1, dtype=bf)) * bf(0.5)
    field = rounded(jnp.dot(sb, inst["b"].astype(bf), preferred_element_type=bf))
    return rounded(pair + field).astype(jnp.float32)


def rounded(x):
    """x rounded to bfloat16, even inside a fusion (XLA may otherwise keep
    bfloat16 results in float32)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
