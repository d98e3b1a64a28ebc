"""Unit-weight MaxCut on a random 3-regular graph: instance and energy.

The graph is a random Hamiltonian cycle plus a random perfect matching
that shares no edge with it, so every vertex has degree 3 (the
construction of the program's `problems.random_3regular_maxcut`, copied
here so that the yardstick owns its instance). Every edge carries J = +1
(antiferromagnetic: E = sum over edges of s_i s_j = edges - 2 cut). The
coloring is greedy first-fit in site order, at most 4 colors. Nothing in
this file but `program_problem` touches the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def edges(n: int, seed: int) -> np.ndarray:
    """(3n/2, 2) edge list of the graph drawn from `seed`."""
    if n < 4 or n % 2:
        raise ValueError(f"a 3-regular graph needs an even n >= 4, got {n}")
    rng = np.random.default_rng(seed)
    order = rng.permutation(n)
    cycle = np.stack([order, np.roll(order, -1)], axis=1)
    cycle_keys = set(map(frozenset, cycle.tolist()))
    for _ in range(1000):
        pairs = rng.permutation(n).reshape(-1, 2)
        if not any(frozenset(p) in cycle_keys for p in pairs.tolist()):
            return np.concatenate([cycle, pairs])
    raise RuntimeError("no perfect matching disjoint from the cycle in 1000 draws")


def greedy_colors(nbr: np.ndarray) -> np.ndarray:
    """(n,) first-fit colors in site order."""
    colors = np.full(nbr.shape[0], -1)
    for i, row in enumerate(nbr):
        used = set(colors[row].tolist())
        c = 0
        while c in used:
            c += 1
        colors[i] = c
    return colors


def make(cfg: dict, seed: int) -> dict:
    """The instance of `cfg` (its size `n`) drawn from `seed`, on the device."""
    n = int(cfg["n"])
    e = edges(n, seed)
    # Each vertex has exactly 3 neighbours: sort both directions by vertex.
    both = np.concatenate([e, e[:, ::-1]])
    both = both[np.lexsort((both[:, 1], both[:, 0]))]
    nbr = both[:, 1].reshape(n, 3).astype(np.int32)
    colors = greedy_colors(nbr)
    masks = np.stack([colors == c for c in range(colors.max() + 1)])
    return {
        "n": n,
        "edges": e,
        "nbr_idx": jnp.asarray(nbr),
        "nbr_w": jnp.ones((n, 3), jnp.float32),
        "b": jnp.zeros((n,), jnp.float32),
        "masks": jnp.asarray(masks),
    }


def program_problem(inst: dict):
    """The same graph, coloring included, as the program's `SparseIsing`."""
    from repro.core.sparse import SparseIsing

    unit = [(int(i), int(j), 1.0) for i, j in inst["edges"]]
    return SparseIsing.from_edges(inst["n"], unit, color_masks=np.asarray(inst["masks"]))


def energy(inst: dict, s: jax.Array, prec: str) -> jax.Array:
    """(R,) energies of the (R, n) ±1 states `s`: float32 ("full", exact
    for these integers) or bfloat16 throughout ("control")."""
    dtype = jnp.float32 if prec == "full" else jnp.bfloat16
    s = s.astype(dtype)
    g = s[:, inst["nbr_idx"]]
    w = inst["nbr_w"].astype(dtype)
    nsum = w[:, 0] * g[..., 0] + w[:, 1] * g[..., 1] + w[:, 2] * g[..., 2]
    pair = rounded(jnp.sum(s * nsum, axis=-1, dtype=dtype)) * dtype(0.5)
    field = rounded(jnp.sum(s * inst["b"].astype(dtype), axis=-1, dtype=dtype))
    return rounded(pair + field).astype(jnp.float32)


def rounded(x):
    """x rounded to its own dtype's precision, even inside a fusion (XLA
    may otherwise keep bfloat16 sums in float32)."""
    info = jnp.finfo(x.dtype)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp, mantissa_bits=info.nmant)
