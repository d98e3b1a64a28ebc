"""Reference chromatic Gibbs sweep on the king's-move lattice, and its least work.

One step is one sweep over the four king colors, color = (y % 2) * 2 +
x % 2, in order. In phase c every site of color c takes +1 when its uniform
lies below P(+1) = sigmoid(-2 (beta h)), h = the 8 neighbours' w_k s_k
added in `OFFSETS` order, plus b; the other sites keep their spin. The
uniforms of a chain at a step are `uniform(split(step_key, 4)[c], (H, W))`,
the stream `run()` documents for the lattice. Written from that definition
alone; states are flat (R, n) outside this module and (R, H, W) inside it.

"full" is float32; "control" computes fields, beta and the sigmoid in
bfloat16, the nearest precision below, each result rounded to bfloat16
(`reduce_precision`, since XLA may otherwise keep float32 inside a fusion).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# The Pallas kernel that does this sweep on the chip, as named in its
# roofline metric.
KERNEL = "lattice_gibbs_sweep"

# The further run() arguments this reference models: `unroll` leaves every
# result as it is (the program documents it bit for bit).
RUN_ARGS = ("unroll",)

OFFSETS = ((-1, -1), (-1, 0), (-1, 1), (0, -1), (0, 1), (1, -1), (1, 0), (1, 1))
N_COLORS = 4


def model_dt(cfg: dict) -> float:
    """Model time one sweep advances (units of 1/lambda0)."""
    return 1.0


def prepare(inst: dict, cfg: dict, prec: str, run_args: dict) -> dict:
    """Weight planes and bias in the dtype of `prec`, and the color masks."""
    dtype = jnp.float32 if prec == "full" else jnp.bfloat16
    H, W = inst["b"].shape
    y, x = np.arange(H)[:, None], np.arange(W)[None, :]
    color = (y % 2) * 2 + x % 2
    return {
        "w": inst["w"].astype(dtype),
        "b": inst["b"].astype(dtype),
        "masks": jnp.asarray(np.stack([color == c for c in range(N_COLORS)])),
    }


def rounded(x):
    """x rounded to its own dtype's precision, even inside a fusion."""
    info = jnp.finfo(x.dtype)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp, mantissa_bits=info.nmant)


def shifted(s: jax.Array, dy: int, dx: int) -> jax.Array:
    """out[..., y, x] = s[..., y + dy, x + dx], zero beyond the lattice."""
    H, W = s.shape[-2:]
    p = jnp.pad(s, [(0, 0)] * (s.ndim - 2) + [(1, 1), (1, 1)])
    return p[..., 1 + dy:1 + dy + H, 1 + dx:1 + dx + W]


def fields(w, b, s):
    """(R, H, W) local fields, the neighbours added in `OFFSETS` order."""
    h = rounded(w[0] * shifted(s, *OFFSETS[0]))
    for k in range(1, len(OFFSETS)):
        h = rounded(h + rounded(w[k] * shifted(s, *OFFSETS[k])))
    return rounded(h + b)


def step(data: dict, s: jax.Array, keys: jax.Array, beta: jax.Array) -> jax.Array:
    """One sweep of the flat (R, n) states `s`; `keys` (R,) and `beta` (R,)."""
    masks = data["masks"]
    dtype = data["w"].dtype
    H, W = masks.shape[1:]
    s = s.reshape(s.shape[0], H, W)
    color_keys = jax.vmap(lambda k: jax.random.split(k, N_COLORS))(keys)
    beta = beta.astype(dtype)[:, None, None]
    for c in range(N_COLORS):
        h = fields(data["w"], data["b"], s.astype(dtype))
        p_up = rounded(jax.nn.sigmoid(rounded(-2.0 * rounded(beta * h)))).astype(jnp.float32)
        u = jax.vmap(lambda k: jax.random.uniform(k, (H, W)))(color_keys[:, c])
        s = jnp.where(masks[c], jnp.where(u < p_up, 1.0, -1.0), s)
    return s.reshape(s.shape[0], H * W)


def work(inst: dict, chains: int) -> tuple[float, float]:
    """(operations, bytes) one sweep of `chains` chains needs at the least.

    16 n operations per chain (a multiply and an add for each of the 8
    neighbours); the 8 float32 weight planes read once for all chains; one
    byte in and one byte out per chain-site. The uniforms (which a kernel
    could draw itself) are not counted.
    """
    n = inst["n"]
    return 16.0 * n * chains, float(32 * n + 2 * chains * n)
