"""±J spin glass on an H x W king's graph: the yardstick's instance and energy.

Every king's-move edge of an open H x W lattice (8 neighbours in the bulk)
carries J = +1 or -1 with equal odds, drawn with numpy from the instance
seed in a fixed order: site by site in row-major order, and at each site
its four forward offsets (0,1), (1,-1), (1,0), (1,1) in that order; a draw
whose neighbour lies beyond the lattice is made and dropped. Zero bias.
The energy is E(s) = sum over edges J_ij s_i s_j + b.s, the program's
convention (1/2 s.J.s + b.s). States are flat (R, n) arrays in row-major
site order, n = H W. Nothing in this file but `program_problem` touches
the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# The king's-move offsets (dy, dx) in the order of the weight planes: plane
# k of site (y, x) couples it to site (y + dy_k, x + dx_k).
OFFSETS = ((-1, -1), (-1, 0), (-1, 1), (0, -1), (0, 1), (1, -1), (1, 0), (1, 1))
FORWARD = OFFSETS[4:]


def shape(cfg: dict) -> tuple[int, int]:
    """(H, W) of `cfg`, which must state n = H W."""
    H, W = int(cfg["H"]), int(cfg["W"])
    if H * W != int(cfg["n"]):
        raise ValueError(f"config states n = {cfg['n']}, but H x W = {H} x {W}")
    return H, W


def planes(H: int, W: int, seed: int) -> np.ndarray:
    """(8, H, W) float32 weight planes of the ±J instance drawn from `seed`."""
    rng = np.random.default_rng(seed)
    J = rng.choice(np.array([-1.0, 1.0]), size=(H, W, len(FORWARD)))
    w = np.zeros((len(OFFSETS), H, W), np.float32)
    for j, (dy, dx) in enumerate(FORWARD):
        ys, xs = np.arange(H), np.arange(W)
        inside_y = ys[(ys + dy >= 0) & (ys + dy < H)]
        inside_x = xs[(xs + dx >= 0) & (xs + dx < W)]
        y, x = np.meshgrid(inside_y, inside_x, indexing="ij")
        w[OFFSETS.index((dy, dx)), y, x] = J[y, x, j]
        w[OFFSETS.index((-dy, -dx)), y + dy, x + dx] = J[y, x, j]
    return w


def make(cfg: dict, seed: int) -> dict:
    """The instance of `cfg` (its `H`, `W` and `n`) drawn from `seed`, on the device."""
    H, W = shape(cfg)
    return {
        "n": H * W,
        "w": jnp.asarray(planes(H, W, seed)),
        "b": jnp.zeros((H, W), jnp.float32),
    }


def program_problem(inst: dict):
    """The same planes as the program's `LatticeIsing`: no clamped or dead site."""
    from repro.core.ising import LatticeIsing

    H, W = inst["b"].shape
    return LatticeIsing(
        w=inst["w"], b=inst["b"],
        clamp_mask=jnp.zeros((H, W), bool),
        clamp_value=-jnp.ones((H, W), jnp.float32),
        dead_mask=jnp.zeros((H, W), bool),
    )


def shifted(s: jax.Array, dy: int, dx: int) -> jax.Array:
    """out[..., y, x] = s[..., y + dy, x + dx], zero beyond the lattice."""
    H, W = s.shape[-2:]
    p = jnp.pad(s, [(0, 0)] * (s.ndim - 2) + [(1, 1), (1, 1)])
    return p[..., 1 + dy:1 + dy + H, 1 + dx:1 + dx + W]


def neighbour_sum(w: jax.Array, s: jax.Array) -> jax.Array:
    """(R, H, W) sums over the 8 neighbours, added in `OFFSETS` order, each
    result rounded to the dtype of `w`."""
    acc = rounded(w[0] * shifted(s, *OFFSETS[0]))
    for k in range(1, len(OFFSETS)):
        acc = rounded(acc + rounded(w[k] * shifted(s, *OFFSETS[k])))
    return acc


def energy(inst: dict, s: jax.Array, prec: str) -> jax.Array:
    """(R,) energies of the flat (R, n) ±1 states `s`: float32 ("full",
    exact for these integers) or bfloat16 throughout ("control")."""
    dtype = jnp.float32 if prec == "full" else jnp.bfloat16
    H, W = inst["b"].shape
    s = s.astype(dtype).reshape(s.shape[0], H, W)
    nsum = neighbour_sum(inst["w"].astype(dtype), s)
    pair = rounded(jnp.sum(s * nsum, axis=(-2, -1), dtype=dtype)) * dtype(0.5)
    field = rounded(jnp.sum(s * inst["b"].astype(dtype), axis=(-2, -1), dtype=dtype))
    return rounded(pair + field).astype(jnp.float32)


def rounded(x):
    """x rounded to its own dtype's precision, even inside a fusion (XLA
    may otherwise keep bfloat16 sums in float32)."""
    info = jnp.finfo(x.dtype)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp, mantissa_bits=info.nmant)
