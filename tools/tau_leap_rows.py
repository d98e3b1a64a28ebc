"""How the chains of a batched `run()` fill the dense tau-leap kernel's rows,
at the `sk2048.anneal` cell's shapes.

Three parts, in one process (on a TPU; anywhere else at whatever size is
given, in interpret mode, which checks results and says nothing of speed):

    jobs    `run()` jobs of the cell's shape (SK n, chains, 300 steps,
            geometric beta 0.1 -> 3, first hit, states every 30 steps):
            the median wall time of a job, ended by `block_until_ready`,
            and `tracing.row_occupancy("tau_leap_step")` after them;
    same    4 chains of a batched `run()` against the `n_chains=1` runs on
            their chain keys, with a shared schedule, and with a per-chain
            schedule and field noise: elements that differ, per output;
    kernel  device time per step of `tau_leap_step` on `chains` rows for
            each (block_n, block_k), and of `chains` one-row calls mapped
            by vmap's default rule (each chain a call of its own, a grid
            axis of the one pallas_call), from a jitted loop of steps.

    python3 tools/tau_leap_rows.py [--n 2048] [--chains 128] [--jobs 8] [--out FILE]

Prints one JSON object, and writes it to `--out` if given.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
ap.add_argument("--n", type=int, default=2048)
ap.add_argument("--chains", type=int, default=128)
ap.add_argument("--steps", type=int, default=300)
ap.add_argument("--jobs", type=int, default=8)
ap.add_argument("--out")
args = ap.parse_args()
out_path = os.path.abspath(args.out) if args.out else None
root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [root, os.path.join(root, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench.problems import sk  # noqa: E402
from repro.core import sampler_api, tracing  # noqa: E402
from repro.core.faults import FaultModel  # noqa: E402
from repro.kernels import ops, tau_leap  # noqa: E402

dev = jax.devices()[0]
on_tpu = dev.platform == "tpu"
if on_tpu:
    jax.config.update("jax_compilation_cache_dir", os.environ.get(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(root, ".jax_cache")))
n, C = args.n, args.chains
problem = sk.program_problem(sk.make({"n": n}, 0))
kernel = sampler_api.TauLeap(dt=0.25)
result = {"device": {"platform": dev.platform, "kind": dev.device_kind}, "n": n, "chains": C}


def job(key, chains=C, **kw):
    """One run() of the cell's shape, waited for."""
    kw = {"n_steps": args.steps, "schedule": sampler_api.geometric(0.1, 3.0),
          "sample_every": 30, "first_hit": -0.67 * n, "backend": "pallas", **kw}
    return jax.block_until_ready(sampler_api.run(problem, kernel, key, n_chains=chains, **kw))


# jobs
job(jax.random.key(0))
walls = []
for i in range(args.jobs):
    t0 = time.perf_counter()
    job(jax.random.key(1 + i))
    walls.append(time.perf_counter() - t0)
occ = tracing.row_occupancy("tau_leap_step")
result["jobs"] = {"job_s_median": statistics.median(walls), "job_s": walls,
                  "row_occupancy": occ._asdict() if occ else None}

# same
same = {}
steps = 30
key = jax.random.key(11)
per_chain = np.asarray(jnp.linspace(0.3, 3.0, 4)[:, None] * jnp.ones((1, steps)))
for name, sched, faults in (("shared_schedule", sampler_api.geometric(0.1, 3.0), None),
                            ("per_chain_schedule_noise", per_chain, FaultModel(field_noise_std=0.3))):
    kw = {"n_steps": steps, "sample_every": 10, "first_hit": -0.5 * n, "faults": faults}
    both = job(key, chains=4, schedule=sched, **kw)
    differ = {f: 0 for f in ("s", "samples", "energies", "t_hit")}
    for c, k in enumerate(jax.random.split(key, 4)):
        one = job(k, chains=1, schedule=sched[c] if sched is per_chain else sched, **kw)
        for f in differ:
            differ[f] += int(np.sum(np.asarray(getattr(both, f))[c] != np.asarray(getattr(one, f))))
    same[name] = differ
result["same"] = same

# kernel
ks = jax.random.split(jax.random.key(3), 2)
s0 = jnp.where(jax.random.bernoulli(ks[0], 0.5, (C, n)), 1.0, -1.0)
u = jax.random.uniform(ks[1], (C, n))
j_i8, scale = ops.quantize_dense(problem.J)
b, dt = problem.b, jnp.asarray(0.25, jnp.float32)
interp = not on_tpu


def per_step(step, reps):
    """Seconds per step of a jitted loop of `reps` steps."""
    loop = jax.jit(lambda s: jax.lax.fori_loop(0, reps, lambda i, s: step(s), s))
    jax.block_until_ready(loop(s0))
    t0 = time.perf_counter()
    jax.block_until_ready(loop(s0))
    return (time.perf_counter() - t0) / reps


blocks = [(128, 128), (256, 256), (512, 512), (512, 1024), (256, 2048), (512, 2048), (1024, 2048)]
kern = {}
for bn, bk in blocks if on_tpu else blocks[:1]:
    step = lambda s, bn=bn, bk=bk: tau_leap.tau_leap_step(
        s, j_i8, b, scale, u, dt, block_n=bn, block_k=bk, interpret=interp)
    kern[f"rows_{bn}x{bk}"] = per_step(step, 200 if on_tpu else 2)
one_row = jax.vmap(lambda s, u: tau_leap.tau_leap_step(
    s[None], j_i8, b, scale, u[None], dt, interpret=interp)[0])
kern["one_row_calls_128x128"] = per_step(lambda s: one_row(s, u), 20 if on_tpu else 2)
result["kernel_s_per_step"] = kern

line = json.dumps(result)
print(line)
if out_path:
    with open(out_path, "w") as f:
        f.write(line + "\n")
