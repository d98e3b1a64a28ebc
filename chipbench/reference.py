"""The plain reference of a job, and the comparison that decides `correct`.

A job is what one `run()` call does: `chains` chains of `steps` steps from
given states or from random ±1 states, under a beta schedule, recording
the states and their energies every `sample_every` steps and, when a
target is given, the first step at which each chain's energy reaches it.
This module redoes that from the definitions alone, importing nothing of
the program. It follows the random stream `run()` documents: the job key
is split into one key per chain; a chain without given states splits off
its init key first; the rest is split into one key per step.

`Reference.simulate` runs whole jobs: in the "control" precision it is the
control, put in the program's place. `Reference.compare` replays each
recorded stretch of a job from the state the program recorded at its
start, with the program's own random stream, and counts where the program
parts from it:

  segments_differing  share of (chain, recorded stretch) pairs whose end
                      state differs from the replay at any site, the final
                      state's agreement with the last sample included;
  energy_gap          widest gap, per spin, between an energy the program
                      recorded and the reference energy of that same state;
  hit_mismatch        share of chains whose first-hit flag, or the step of
                      the hit, differs from the replay's.

A site whose uniform lies within rounding of its flip probability may go
either way, and it then changes later decisions, so sound runs part from
the replay in a few stretches; a wrong step, schedule, stream or state
parts from it in most.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("s", "samples", "energies", "hit", "t_hit"),
    meta_fields=(),
)
@dataclasses.dataclass
class Outputs:
    """What a job hands back: final states (B, n); samples (B, M, n) and
    energies (B, M) when it records; first-hit flags and model times (B,)
    when it tracks a target."""

    s: Any
    samples: Any = None
    energies: Any = None
    hit: Any = None
    t_hit: Any = None


def betas(schedule: dict, steps: int) -> jax.Array:
    """(steps,) inverse temperatures of a schedule entry of a traffic mix."""
    kind = schedule["kind"]
    if kind == "constant":
        return jnp.full((steps,), schedule["beta"], jnp.float32)
    if kind == "geometric":
        b0, b1 = schedule["beta0"], schedule["beta1"]
        return b0 * (b1 / b0) ** jnp.linspace(0.0, 1.0, steps)
    raise ValueError(f"unknown schedule kind {kind!r}")


@partial(jax.jit, static_argnames=("chains", "steps", "given"))
def streams(job_key, chains: int, steps: int, given: bool):
    """Init keys (B,) and step keys (B, steps) of a job's chains."""

    def one(k):
        if given:
            return k, jax.random.split(k, steps)
        k, k_init = jax.random.split(k)
        return k_init, jax.random.split(k, steps)

    return jax.vmap(one)(jax.random.split(job_key, chains))


@partial(jax.jit, static_argnames=("n",))
def random_states(init_keys, n: int):
    """(B, n) uniform random ±1 states, one chain per key."""
    return jax.vmap(
        lambda k: (2 * jax.random.bernoulli(k, 0.5, (n,)) - 1).astype(jnp.float32)
    )(init_keys)


class Reference:
    """Jobs of one cell redone from the definitions, in one precision."""

    def __init__(self, problem, dynamics, cfg: dict, inst: dict, traffic: dict,
                 target: Optional[float], run_args: dict, prec: str):
        unknown = set(run_args) - set(dynamics.RUN_ARGS)
        if unknown:
            raise ValueError(f"the reference does not model run() arguments {sorted(unknown)}")
        self.n = inst["n"]
        self.traffic = traffic
        self.dt = dynamics.model_dt(cfg)
        self.target = jnp.float32(np.inf if target is None else target)
        self.track = target is not None
        arrays = {k: v for k, v in inst.items() if isinstance(v, jax.Array)}
        self.arrays = arrays
        self.data = dynamics.prepare(inst, cfg, prec, run_args)
        self.betas = betas(traffic["schedule"], traffic["steps"])
        self._energy = jax.jit(lambda a, s: problem.energy(a, s, prec))

        def segment(arrays, data, s, keys, betas, target):
            length = keys.shape[1]

            def body(carry, x):
                s, first = carry
                k, beta, i = x
                s = dynamics.step(data, s, k, beta)
                if self.track:
                    e = problem.energy(arrays, s, prec)
                    first = jnp.where((e <= target) & (first == length), i, first)
                return (s, first), None

            first = jnp.full(s.shape[:1], length, jnp.int32)
            xs = (keys.T, betas.T, jnp.arange(length, dtype=jnp.int32))
            (s, first), _ = jax.lax.scan(body, (s, first), xs)
            return s, first

        self._segment = jax.jit(segment)

    def energy(self, s):
        """(R,) energies of (R, n) states, in this reference's precision."""
        return self._energy(self.arrays, s)

    def _stretch(self):
        t = self.traffic
        k = t["sample_every"] or t["steps"]
        return k, t["steps"] // k

    def _start(self, job_key, s0):
        t = self.traffic
        init_keys, step_keys = streams(job_key, t["chains"], t["steps"], s0 is not None)
        s = random_states(init_keys, self.n) if s0 is None else jnp.asarray(s0)
        return s, step_keys

    def simulate(self, job_key, s0=None) -> Outputs:
        """One whole job, as `run()` would hand it back."""
        t = self.traffic
        s, step_keys = self._start(job_key, s0)
        k, m_count = self._stretch()
        chains = t["chains"]
        hit_step = jnp.where(self.energy(s) <= self.target, 0, -1) if self.track else None
        samples, energies = [], []
        for m in range(m_count):
            bet = jnp.broadcast_to(self.betas[m * k:(m + 1) * k], (chains, k))
            s, first = self._segment(
                self.arrays, self.data, s, step_keys[:, m * k:(m + 1) * k], bet, self.target
            )
            if self.track:
                hit_step = jnp.where((hit_step < 0) & (first < k), m * k + first + 1, hit_step)
            if t["sample_every"]:
                samples.append(s)
                energies.append(self.energy(s))
        out = Outputs(s=s)
        if t["sample_every"]:
            out.samples = jnp.stack(samples, axis=1)
            out.energies = jnp.stack(energies, axis=1)
        if self.track:
            out.hit = hit_step >= 0
            out.t_hit = jnp.where(out.hit, hit_step * jnp.float32(self.dt), jnp.inf)
        return out

    def compare(self, job_key, s0, out: Outputs) -> dict:
        """Counts of where the program's job `out` parts from the replay."""
        t = self.traffic
        s_init, step_keys = self._start(job_key, s0)
        k, m_count = self._stretch()
        chains, n = t["chains"], self.n
        final = jnp.asarray(out.s)
        if t["sample_every"]:
            ends = jnp.asarray(out.samples)
            starts = jnp.concatenate([s_init[:, None], ends[:, :-1]], axis=1)
        else:
            ends, starts = final[:, None], s_init[:, None]
        rows = chains * m_count
        bet = jnp.broadcast_to(self.betas.reshape(m_count, k), (chains, m_count, k))
        s_end, first = self._segment(
            self.arrays, self.data, starts.reshape(rows, n),
            step_keys.reshape(rows, k), bet.reshape(rows, k), self.target,
        )
        differ = np.asarray(jnp.any(s_end != ends.reshape(rows, n), axis=-1))
        counts = {"segments": rows, "segments_differing": int(differ.sum())}
        if t["sample_every"]:
            last = np.asarray(jnp.any(final != ends[:, -1], axis=-1))
            counts["segments"] += chains
            counts["segments_differing"] += int(last.sum())
            e_ref = np.asarray(self.energy(ends.reshape(rows, n))).reshape(chains, m_count)
            gap = np.abs(np.asarray(out.energies, np.float64) - e_ref) / n
            counts["energy_gap"] = float(np.nan_to_num(gap, nan=1e30, posinf=1e30).max())
        if self.track:
            first = np.asarray(first).reshape(chains, m_count)
            ref_step = np.full(chains, -1)
            for m in reversed(range(m_count)):
                ref_step = np.where(first[:, m] < k, m * k + first[:, m] + 1, ref_step)
            ref_step = np.where(np.asarray(self.energy(s_init)) <= float(self.target), 0, ref_step)
            hit = np.asarray(out.hit)
            prog_step = np.where(hit, np.rint(np.asarray(out.t_hit) / self.dt), -1)
            mismatch = (hit != (ref_step >= 0)) | (hit & (prog_step != ref_step))
            counts["chains"] = chains
            counts["hit_mismatch"] = int(mismatch.sum())
        return counts


def readings(counts: list[dict]) -> dict:
    """The compared numbers over the checked jobs' counts."""
    total = {}
    for c in counts:
        for key, v in c.items():
            total[key] = max(total.get(key, 0.0), v) if key == "energy_gap" else total.get(key, 0) + v
    out = {"segments_differing": total["segments_differing"] / total["segments"]}
    if "energy_gap" in total:
        out["energy_gap"] = total["energy_gap"]
    if "hit_mismatch" in total:
        out["hit_mismatch"] = total["hit_mismatch"] / total["chains"]
    return out
