"""Reference chromatic Gibbs sweep on a sparse graph, and its least work.

One step is one sweep over the color classes in their order. In class c
every site of the class takes +1 when its uniform lies below
P(+1) = sigmoid(-2 beta h), h = sum_k w_ik s_{nbr_ik} + b_i, with the
slots added in order k = 0, 1, ...; the other sites keep their spin. The
uniforms of a chain at a step are `uniform(split(step_key, C)[c], (n,))`,
the stream `run()` documents. Written from that definition alone.

"full" is float32; "control" computes fields, beta and the sigmoid in
bfloat16, the nearest precision below, each result rounded to bfloat16
(`reduce_precision`, since XLA may otherwise keep float32 inside a fusion).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# The Pallas kernel that does this sweep on the chip, as named in its
# roofline metric.
KERNEL = "colored_gibbs_sweep"

# The further run() arguments this reference models: `unroll` leaves every
# result as it is (the program documents it bit for bit).
RUN_ARGS = ("unroll",)


def model_dt(cfg: dict) -> float:
    """Model time one sweep advances (units of 1/lambda0)."""
    return 1.0


def prepare(inst: dict, cfg: dict, prec: str, run_args: dict) -> dict:
    """Neighbour tables and color masks in the dtype of `prec`."""
    dtype = jnp.float32 if prec == "full" else jnp.bfloat16
    return {
        "nbr": inst["nbr_idx"],
        "w": inst["nbr_w"].astype(dtype),
        "b": inst["b"].astype(dtype),
        "masks": inst["masks"],
    }


def rounded(x):
    """x rounded to its own dtype's precision, even inside a fusion."""
    info = jnp.finfo(x.dtype)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp, mantissa_bits=info.nmant)


def fields(nbr, w, b, s):
    """(R, n) local fields, slots added in order."""
    g = s[:, nbr]  # (R, n, max_deg)
    h = rounded(w[:, 0] * g[..., 0])
    for k in range(1, w.shape[1]):
        h = rounded(h + rounded(w[:, k] * g[..., k]))
    return rounded(h + b)


def step(data: dict, s: jax.Array, keys: jax.Array, beta: jax.Array) -> jax.Array:
    """One sweep of the (R, n) states `s`; `keys` (R,) and `beta` (R,)."""
    masks = data["masks"]
    dtype = data["w"].dtype
    n_colors = masks.shape[0]
    color_keys = jax.vmap(lambda k: jax.random.split(k, n_colors))(keys)
    beta = beta.astype(dtype)[:, None]
    for c in range(n_colors):
        h = fields(data["nbr"], data["w"], data["b"], s.astype(dtype))
        p_up = rounded(jax.nn.sigmoid(rounded(-2.0 * rounded(beta * h)))).astype(jnp.float32)
        u = jax.vmap(lambda k: jax.random.uniform(k, s.shape[1:]))(color_keys[:, c])
        s = jnp.where(masks[c], jnp.where(u < p_up, 1.0, -1.0), s)
    return s


def work(inst: dict, chains: int) -> tuple[float, float]:
    """(operations, bytes) one sweep of `chains` chains needs at the least.

    2 n max_deg operations per chain; the neighbour tables at their stored
    widths (int32 index, float32 weight) read once for all chains; one byte
    in and one byte out per chain-site.
    """
    n, max_deg = inst["nbr_idx"].shape
    return 2.0 * n * max_deg * chains, float(n * max_deg * (4 + 4) + 2 * chains * n)
