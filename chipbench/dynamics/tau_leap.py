"""Reference tau-leap step on a dense problem, and the kernel's least work.

The step is the program's documented asynchronous model, written here
from its definition alone: with fields h = beta * (J_q s + b), where J_q
is J on a symmetric per-tensor grid of `bits` (scale max|J| / (2^(bits-1)
- 1), round to nearest), every spin flips independently when its uniform
lies below p = 1 - exp(-dt * sigmoid(2 h s)). The uniform of a chain at a
step is `uniform(step_key, (n,))`, the stream `run()` documents.

"full" holds the couplings at the configuration's int8; "control" at
int4, the nearest precision below.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

# The Pallas kernel that does this step on the chip, as named in its
# roofline metric.
KERNEL = "tau_leap_step"

# The further run() arguments this reference models: `unroll` leaves every
# result as it is (the program documents it bit for bit).
RUN_ARGS = ("unroll",)


def model_dt(cfg: dict) -> float:
    """Model time one step advances (units of 1/lambda0)."""
    return float(cfg["kernel_args"]["dt"])


def prepare(inst: dict, cfg: dict, prec: str, run_args: dict) -> dict:
    """Quantized couplings for `prec`, kept as exact float32 integers."""
    bits = 8 if prec == "full" else 4
    qmax = float(2 ** (bits - 1) - 1)
    J = inst["J"]
    scale = jnp.max(jnp.abs(J)) / qmax
    scale = jnp.where(scale == 0, 1.0, scale)
    codes = jnp.clip(jnp.round(J / scale), -qmax, qmax)
    return {"codes": codes, "scale": scale, "b": inst["b"], "dt": model_dt(cfg)}


def step(data: dict, s: jax.Array, keys: jax.Array, beta: jax.Array) -> jax.Array:
    """One step of the (R, n) states `s`; `keys` (R,) and `beta` (R,)."""
    # |acc| <= n * 127 < 2**24, so the float32 product is exact.
    acc = jnp.dot(s, data["codes"].T, precision=HIGHEST)
    beta = beta[:, None]
    h = acc * (beta * data["scale"]) + beta * data["b"]
    p_flip = 1.0 - jnp.exp(-data["dt"] * jax.nn.sigmoid(2.0 * h * s))
    u = jax.vmap(lambda k: jax.random.uniform(k, s.shape[1:]))(keys)
    return jnp.where(u < p_flip, -s, s)


def work(inst: dict, chains: int) -> tuple[float, float]:
    """(operations, bytes) one step of `chains` chains needs at the least.

    2 n^2 operations per chain; the int8 couplings read once for all
    chains; one byte in and one byte out per chain-site. Padding and the
    uniforms (which a kernel could draw itself) are not counted.
    """
    n = inst["n"]
    return 2.0 * n * n * chains, float(n * n + 2 * chains * n)
