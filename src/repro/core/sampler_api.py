"""Unified sampler API: step-kernel / driver split.

The paper's single asynchronous Glauber dynamic serves combinatorial
optimization, neural simulation, and ML training alike.  This module
expresses that one dynamic once: a small `SamplerKernel` protocol (how one
step of a chain advances) and ONE `run()` driver that owns everything every
sampling entry point used to re-implement — the `lax.scan`, observation
striding, energy recording, beta schedules, first-hit TTS tracking,
multi-chain batching, and backend dispatch onto the Pallas kernels.

Kernel protocol (state is a `KernelState` pytree):

    kernel.init(problem, key, s0=None, faults=None) -> KernelState
    kernel.step(problem, state, key, beta, faults=None) -> KernelState

(the driver only passes `faults` when `run(..., faults=...)` is given a
non-None `repro.core.faults.FaultModel`, so kernels that never heard of
faults — and the fault-free program — are untouched).

Kernels implemented here, registered by name for config/benchmark selection:

    "random_scan_gibbs" — the paper's SYNCHRONOUS baseline (dense problems):
        one uniformly random site resampled per step, incremental fields,
        model time 1/lambda0 per step.
    "chromatic_gibbs"   — exact parallel Gibbs on the king's-move lattice via
        the 4-coloring; one step = one sweep = 4 color phases.  Under
        `backend="pallas"` the whole sweep runs as ONE fused Pallas
        `lattice_gibbs_sweep` call (lattice + weights VMEM-resident), the
        chip's colored update groups; the ref path recomputes the stencil
        field per color phase.
    "colored_gibbs"     — chromatic Gibbs on ARBITRARY sparse graphs
        (`SparseIsing` + its greedy-coloring `color_masks`); one step = one
        sweep over the color classes with vectorized neighbor gathers.
        Under `backend="pallas"` the sweep runs as ONE fused
        `colored_gibbs_sweep` call (neighbor tables VMEM-resident).
    "tau_leap"          — the PASS ASYNC model (lattice, dense, or sparse;
        ref path for non-dense): every
        neuron flips independently w.p. 1-exp(-dt*lambda_i) per step of
        model time dt.  dt*lambda0 -> 0 recovers the exact CTMC.  The dense
        form dispatches to the Pallas `tau_leap_step` kernel via
        `backend="pallas"` (int8 MXU matmul, fused flip epilogue).
    "ctmc"              — the exact event-driven CTMC (Gillespie); one step =
        one flip event, stochastic model-time advance.  `site_draw` selects
        event selection: the O(n) categorical ("scan") or the sum-tree
        descent ("tree": ONE uniform + O(log n), tree maintained in the
        kernel state — see `repro.core.event_tree`); "auto" picks by size.
        On `SparseIsing` the tree path repairs only the <= max_deg affected
        leaves per event (`event_tree.update_many`): O(deg log n) per flip.

Driver:

    run(problem, kernel, key, n_steps=..., schedule=..., n_chains=...,
        sample_every=..., first_hit=..., backend=...) -> RunResult

`schedule` accepts None (beta=1), a float, a `(n_steps,)` array, a
`(n_chains, n_steps)` array (per-chain schedules — replica exchange), or a
Schedule object (`constant` / `linear` / `geometric`).  `backend` is
`"ref" | "pallas" | "auto"`: an explicit "pallas" request on a kernel (or
kernel/problem combination) with no Pallas path raises ValueError instead
of silently running the ref path; "auto" picks the best backend the kernel
supports on this platform (compiled Pallas on TPU, reference elsewhere).
The legacy entry points in `samplers` / `annealing` / `ctmc` are thin
deprecated wrappers over this driver and reproduce their historical
outputs bit-for-bit at beta=1.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, NamedTuple, Optional, Protocol, Union, runtime_checkable

import jax
import jax.numpy as jnp

from repro.core import diagnostics as diag
from repro.core import event_tree, glauber, tracing
from repro.core.diagnostics import RunDiagnostics  # noqa: F401  (re-export)
from repro.core.faults import FaultModel  # noqa: F401  (re-export)
from repro.core.ising import DenseIsing, LatticeIsing, king_color_masks
from repro.core.sparse import SparseIsing


class NonFiniteEnergyError(ValueError):
    """A problem (or an over-aggressive fault model) has non-finite energy.

    Raised by `run()` before any sampling happens: a NaN/Inf coupling or
    bias would otherwise silently poison every recorded energy and produce
    NaN TTS fits downstream (`observables.fit_scaling`)."""


def random_init(key: jax.Array, shape, dtype=jnp.float32) -> jax.Array:
    """Uniform random ±1 initial state (the chip's post-reset state)."""
    return (2 * jax.random.bernoulli(key, 0.5, shape) - 1).astype(dtype)


def state_shape(problem) -> tuple[int, ...]:
    """Natural spin-array shape for a problem."""
    return problem.shape if isinstance(problem, LatticeIsing) else (problem.n,)


def problem_kind_of(problem) -> str:
    """The problem-kind dispatch axis: "dense" | "lattice" | "sparse".

    Kernels declare the kinds they implement via a `problem_kinds` class
    attribute; `run()` checks the pair up front so an unsupported
    combination fails with a readable error instead of a shape error deep
    inside a jitted step function."""
    if isinstance(problem, LatticeIsing):
        return "lattice"
    if isinstance(problem, SparseIsing):
        return "sparse"
    return "dense"


def kernel_problem_kinds(kernel) -> tuple[str, ...]:
    """Problem kinds a kernel implements (all three when undeclared)."""
    return getattr(type(kernel), "problem_kinds", ("dense", "lattice", "sparse"))


def check_problem_kind(kernel, problem) -> None:
    """Raise ValueError when `kernel` does not implement `problem`'s kind."""
    kinds = kernel_problem_kinds(kernel)
    kind = problem_kind_of(problem)
    if kind not in kinds:
        name = getattr(kernel, "name", type(kernel).__name__)
        raise ValueError(
            f"kernel {name!r} does not support {kind!r} problems; "
            f"supported problem kinds: {kinds}"
        )


def _apply_field_delta(problem, h, i, delta):
    """Incremental local-field update after s_i changes by `delta`.

    Dense: add the full J row — O(n). Sparse: scatter-add the <= max_deg
    neighbor contributions — O(max_deg); padded slots carry zero weight so
    the (duplicate-safe) scatter needs no degree mask. Either way h_i itself
    is untouched (symmetric J, zero diagonal)."""
    if isinstance(problem, SparseIsing):
        return h.at[problem.nbr_idx[i]].add(problem.nbr_w[i] * delta)
    return h + problem.J[:, i] * delta


# ---------------------------------------------------------------------------
# Kernel state & protocol
# ---------------------------------------------------------------------------


class KernelState(NamedTuple):
    """Pytree carried through the driver's scan.

    s:   spin state (±1), shape = problem's natural shape.
    t:   model time (seconds of chip time at rate lambda0).
    e:   running energy E(s) for kernels that maintain it incrementally
         (random-scan, ctmc); None otherwise — the driver recomputes on
         demand for first-hit tracking.
    aux: kernel-private pytree (incremental local fields, quantized weights).
    """

    s: jax.Array
    t: jax.Array
    e: Any
    aux: Any


@runtime_checkable
class SamplerKernel(Protocol):
    """One MCMC/CTMC step rule. Implementations are frozen dataclasses
    registered as pytrees: float/str config is metadata (static under jit),
    array-valued config (e.g. sigmoid trims) is data.

    The optional `faults` argument (a `repro.core.faults.FaultModel`
    residual, pre-bound by the driver) carries the dynamic device faults a
    step must emulate; the driver only passes it when it is not None, so
    kernels that predate the fault layer keep working and the fault-free
    program is byte-identical to the pre-fault one."""

    def init(
        self, problem, key: jax.Array, s0: Optional[jax.Array] = None, faults=None
    ) -> KernelState:
        """Build the initial kernel state (random init when s0 is None)."""
        ...

    def step(
        self, problem, state: KernelState, key: jax.Array, beta: jax.Array, faults=None
    ) -> KernelState:
        """Advance the chain by one kernel step at inverse temperature beta."""
        ...


# ---------------------------------------------------------------------------
# Kernel registry
# ---------------------------------------------------------------------------

KERNELS: dict[str, type] = {}


def register_kernel(name: str):
    """Class decorator: register a kernel under `name` for by-name lookup
    (configs, benchmarks, CLI flags)."""

    def deco(cls):
        """Register `cls` and attach its registry name."""
        KERNELS[name] = cls
        cls.name = name
        return cls

    return deco


def get_kernel(name: str, **config) -> "SamplerKernel":
    """Instantiate a registered kernel by name."""
    if name not in KERNELS:
        raise KeyError(f"unknown sampler kernel {name!r}; have {sorted(KERNELS)}")
    return KERNELS[name](**config)


def kernel_names() -> list[str]:
    """Sorted names of all registered kernels."""
    return sorted(KERNELS)


# ---------------------------------------------------------------------------
# Beta schedules (subsumes annealing.py's ramp zoo)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Schedule:
    """Base: a schedule maps n_steps -> (n_steps,) array of betas."""

    def betas(self, n_steps: int) -> jax.Array:
        """Materialize the (n_steps,) beta array."""
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class constant(Schedule):
    """Constant-beta schedule."""
    beta: float = 1.0

    def betas(self, n_steps: int) -> jax.Array:
        """Materialize the (n_steps,) beta array."""
        return jnp.full((n_steps,), self.beta, jnp.float32)


@dataclasses.dataclass(frozen=True)
class linear(Schedule):
    """Linear beta ramp from beta0 to beta1."""
    beta0: float = 0.3
    beta1: float = 2.0

    def betas(self, n_steps: int) -> jax.Array:
        """Materialize the (n_steps,) beta array."""
        return jnp.linspace(self.beta0, self.beta1, n_steps)


@dataclasses.dataclass(frozen=True)
class geometric(Schedule):
    """Geometric beta ramp from beta0 to beta1."""
    beta0: float = 0.3
    beta1: float = 2.0

    def betas(self, n_steps: int) -> jax.Array:
        """Materialize the (n_steps,) beta array."""
        return self.beta0 * (self.beta1 / self.beta0) ** jnp.linspace(0.0, 1.0, n_steps)


ScheduleLike = Union[None, float, jax.Array, Schedule]


def _tau_leap_flip(s, h, key, dt, trim, frozen, keep=None):
    """One tau-leap update given (beta-scaled) fields h: each spin flips
    w.p. 1-exp(-dt*lambda_i/lambda0); frozen (clamped/dead/stuck) sites
    never do, and sites outside `keep` (update dropout) lose their flip
    AFTER the uniform is drawn — the random stream does not depend on the
    dropout draw, only the realized flips do."""
    rate = glauber.flip_prob(h, s, trim)
    p_flip = 1.0 - jnp.exp(-dt * rate)
    if frozen is not None:
        p_flip = jnp.where(frozen, 0.0, p_flip)
    flips = jax.random.uniform(key, s.shape) < p_flip
    if keep is not None:
        flips = flips & keep
    return jnp.where(flips, -s, s)


def resolve_schedule(
    schedule: ScheduleLike, n_steps: int, n_chains: Optional[int] = None
) -> jax.Array:
    """Normalize any accepted schedule form to a beta array.

    Returns (n_steps,) — or (n_chains, n_steps) when given a 2D array of
    per-chain schedules. When `n_chains` is given (as `run()` does), a 2D
    schedule's row count is validated against it HERE, with an error naming
    both numbers — not left to surface as a vmap axis error deep in the
    driver."""
    if schedule is None:
        return jnp.ones((n_steps,), jnp.float32)
    if isinstance(schedule, Schedule):
        return schedule.betas(n_steps)
    if isinstance(schedule, (int, float)):
        return jnp.full((n_steps,), float(schedule), jnp.float32)
    betas = jnp.asarray(schedule, jnp.float32)
    if betas.ndim == 0:  # numpy/jax scalar: constant schedule
        return jnp.full((n_steps,), betas)
    if betas.ndim > 2:
        raise ValueError(
            f"schedule must be scalar, (n_steps,), or (n_chains, n_steps); "
            f"got shape {betas.shape}"
        )
    if betas.shape[-1] != n_steps:
        raise ValueError(f"schedule length {betas.shape[-1]} != n_steps {n_steps}")
    if betas.ndim == 2 and n_chains is not None:
        if n_chains == 1:
            raise ValueError(
                f"per-chain schedule of shape {betas.shape} requires "
                f"n_chains > 1 (got n_chains=1)"
            )
        if betas.shape[0] != n_chains:
            raise ValueError(
                f"per-chain schedule has {betas.shape[0]} rows but run() was "
                f"asked for n_chains={n_chains}"
            )
    return betas


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------


@register_kernel("random_scan_gibbs")
@partial(jax.tree_util.register_dataclass, data_fields=(), meta_fields=("lambda0",))
@dataclasses.dataclass(frozen=True)
class RandomScanGibbs:
    """Serial random-scan Gibbs on a dense problem — the paper's synchronous
    baseline. One site per step, dt = 1/lambda0 per step (the chip
    comparison runs the serial system at the single-neuron rate).
    Maintains local fields and energy incrementally: O(n) per step for
    dense problems, O(max_deg) for sparse ones."""

    problem_kinds = ("dense", "sparse")

    lambda0: float = 1.0

    def init(self, problem, key, s0=None, faults=None) -> KernelState:
        """Initial state with incremental fields and energy."""
        if s0 is None:
            s0 = random_init(key, state_shape(problem))
        if faults is not None:
            s0 = faults.apply_stuck(s0)
        return KernelState(
            s=s0,
            t=jnp.asarray(0.0, jnp.float32),
            e=problem.energy(s0),
            aux=problem.local_fields(s0),
        )

    def step(self, problem, state, key, beta, faults=None) -> KernelState:
        """Resample one uniformly random site from its conditional."""
        s, h = state.s, state.aux
        k_site, k_flip = jax.random.split(key)
        if faults is not None and (faults.noisy or faults.drops):
            k_flip, k_noise, k_drop = jax.random.split(k_flip, 3)
        i = jax.random.randint(k_site, (), 0, problem.n)
        hi = h[i]
        if faults is not None and faults.noisy:
            hi = hi + faults.field_noise(k_noise, ())
        p_up = glauber.prob_up(beta * hi)
        new_si = jnp.where(jax.random.uniform(k_flip) < p_up, 1.0, -1.0)
        if faults is not None:
            # A stuck site or a dropped update keeps the previous value:
            # delta = 0, so the incremental energy/field stay exact.
            suppress = None
            if faults.drops:
                suppress = jax.random.uniform(k_drop) < faults.dropout
            stuck = faults.stuck_flat()
            if stuck is not None:
                suppress = stuck[i] if suppress is None else (suppress | stuck[i])
            if suppress is not None:
                new_si = jnp.where(suppress, s[i], new_si)
        delta = new_si - s[i]
        # dE for changing s_i by delta: delta * h_i (h is the raw, beta-free
        # field including b and the full J row)
        e = state.e + delta * h[i]
        h = _apply_field_delta(problem, h, i, delta)
        s = s.at[i].set(new_si)
        return KernelState(s=s, t=state.t + 1.0 / self.lambda0, e=e, aux=h)


@register_kernel("chromatic_gibbs")
@partial(
    jax.tree_util.register_dataclass,
    data_fields=("trim",),
    meta_fields=("lambda0", "backend"),
)
@dataclasses.dataclass(frozen=True)
class ChromaticGibbs:
    """Exact parallel Gibbs on the king's-move lattice via the 4-coloring.
    One step = 4 color phases = one update per neuron, so the equivalent
    model time per step at per-neuron rate lambda0 is 1/lambda0.

    `backend="pallas"` routes the whole sweep through the fused Pallas
    `lattice_gibbs_sweep` kernel (all 4 color phases with lattice + weights
    resident in VMEM; compiled on TPU, interpreted elsewhere). The ref path
    recomputes the full stencil field once per color phase in plain jnp.
    Both paths draw the same per-color uniforms from the same key split, so
    they agree bit-for-bit in interpret mode.

    Lattice-only: the arbitrary-graph generalization is `colored_gibbs`
    (sparse problems with `color_masks`)."""

    backends = ("ref", "pallas")
    problem_kinds = ("lattice",)

    lambda0: float = 1.0
    trim: Optional[glauber.SigmoidTrim] = None
    backend: str = "ref"  # "ref" | "pallas"

    def backends_for(self, problem) -> tuple[str, ...]:
        # trims are a ref-only feature, so "auto" must not pick pallas
        """Backends valid for this kernel config (trims are ref-only)."""
        return ("ref",) if self.trim is not None else self.backends

    def init(self, problem: LatticeIsing, key, s0=None, faults=None) -> KernelState:
        """Initial state on the clamped lattice (stuck sites arrive already
        absorbed into the clamp masks via `FaultModel.bind`)."""
        if self.backend == "pallas" and self.trim is not None:
            raise NotImplementedError(
                "pallas chromatic gibbs does not support trims"
            )
        if s0 is None:
            s0 = random_init(key, state_shape(problem))
        s0 = problem.apply_clamps(s0)
        return KernelState(s=s0, t=jnp.asarray(0.0, jnp.float32), e=None, aux=())

    def step(self, problem: LatticeIsing, state, key, beta, faults=None) -> KernelState:
        """One sweep: all 4 king-coloring phases.

        Field noise is one per-step draw applied as a bias perturbation
        (shared by the 4 phases — both backends then evaluate the same
        expression); dropped sites are removed from their color class for
        this sweep; stuck sites were folded into `frozen_mask` by bind."""
        H, W = problem.shape
        colors = king_color_masks(H, W)
        frozen = problem.frozen_mask
        s = state.s
        eta = keep = None
        if faults is not None and (faults.noisy or faults.drops):
            key, k_noise, k_drop = jax.random.split(key, 3)
            if faults.noisy:
                eta = faults.field_noise(k_noise, s.shape)
            if faults.drops:
                keep = faults.keep_mask(k_drop, s.shape)
        keys = jax.random.split(key, colors.shape[0])
        if self.backend == "pallas":
            # trim is rejected in init(), which every driver path runs first
            from repro.kernels import ops

            u = jnp.stack(
                [jax.random.uniform(keys[c], s.shape) for c in range(colors.shape[0])]
            )
            update = colors if keep is None else colors & keep
            s = ops.lattice_gibbs_sweep(
                s[None],
                problem.w,
                problem.b if eta is None else problem.b + eta,
                u[:, None],
                update.astype(s.dtype),
                frozen.astype(s.dtype),
                problem.frozen_values.astype(s.dtype),
                beta=beta,
                mode="kernel",
            )[0]
        else:
            prob = (
                problem if eta is None
                else dataclasses.replace(problem, b=problem.b + eta)
            )
            for c in range(colors.shape[0]):
                h = prob.local_fields(s)
                p_up = glauber.prob_up(beta * h, self.trim)
                u = jax.random.uniform(keys[c], s.shape)
                proposal = jnp.where(u < p_up, 1.0, -1.0).astype(s.dtype)
                upd = colors[c] & (~frozen)
                if keep is not None:
                    upd = upd & keep
                s = jnp.where(upd, proposal, s)
            s = problem.apply_clamps(s)
        return KernelState(s=s, t=state.t + 1.0 / self.lambda0, e=None, aux=())


@register_kernel("colored_gibbs")
@partial(
    jax.tree_util.register_dataclass,
    data_fields=(),
    meta_fields=("lambda0", "backend"),
)
@dataclasses.dataclass(frozen=True)
class ColoredGibbs:
    """Exact parallel Gibbs on an arbitrary sparse graph via its coloring —
    `chromatic_gibbs` generalized beyond the king's lattice. The problem's
    `color_masks` partition the sites into independent sets (greedy
    `color_graph` at construction, or a known coloring like the king
    4-coloring), so same-color conditionals are independent and one step =
    one full sweep over the color classes = one update per site (model time
    1/lambda0 per sweep, like `chromatic_gibbs`).

    `backend="pallas"` routes the whole sweep through the fused
    `colored_gibbs_sweep` kernel (neighbor tables VMEM-resident, all color
    phases in one pallas_call; compiled on TPU, interpreted elsewhere). The
    ref path recomputes the gathered fields once per color phase in plain
    jnp. Both paths draw the same per-color uniforms from the same key
    split and evaluate the identical gather+reduce expression, so they
    agree bit-for-bit in interpret mode."""

    backends = ("ref", "pallas")
    problem_kinds = ("sparse",)

    lambda0: float = 1.0
    backend: str = "ref"  # "ref" | "pallas"

    def init(self, problem: SparseIsing, key, s0=None, faults=None) -> KernelState:
        """Initial state; requires the problem's color_masks."""
        if getattr(problem, "color_masks", None) is None:
            raise ValueError(
                "colored_gibbs needs problem.color_masks — build the problem "
                "with coloring enabled (SparseIsing.from_edges/from_dense "
                "color by default) or supply masks explicitly"
            )
        if s0 is None:
            s0 = random_init(key, state_shape(problem))
        if faults is not None:
            s0 = faults.apply_stuck(s0)
        return KernelState(s=s0, t=jnp.asarray(0.0, jnp.float32), e=None, aux=())

    def step(self, problem: SparseIsing, state, key, beta, faults=None) -> KernelState:
        """One sweep over the graph's color classes.

        Faults fold into the color masks (stuck/dropped sites leave their
        color class for this sweep) and into the bias (one per-sweep field-
        noise draw shared by all phases), identically on both backends."""
        masks = problem.color_masks  # (C, n) bool
        s = state.s
        eta = keep = None
        if faults is not None and (faults.noisy or faults.drops):
            key, k_noise, k_drop = jax.random.split(key, 3)
            if faults.noisy:
                eta = faults.field_noise(k_noise, s.shape)
            if faults.drops:
                keep = faults.keep_mask(k_drop, s.shape)
        stuck = faults.stuck_flat() if faults is not None else None
        if stuck is not None:
            masks = masks & ~stuck  # (C, n) & (n,) broadcasts per color
        if keep is not None:
            masks = masks & keep
        keys = jax.random.split(key, masks.shape[0])
        if self.backend == "pallas":
            from repro.kernels import ops

            u = jnp.stack(
                [jax.random.uniform(keys[c], s.shape) for c in range(masks.shape[0])]
            )
            s = ops.colored_gibbs_sweep(
                s[None],
                problem.nbr_idx,
                problem.nbr_w,
                problem.b if eta is None else problem.b + eta,
                u[:, None],
                masks.astype(s.dtype),
                beta=beta,
                mode="kernel",
            )[0]
        else:
            prob = (
                problem if eta is None
                else dataclasses.replace(problem, b=problem.b + eta)
            )
            for c in range(masks.shape[0]):
                h = prob.local_fields(s)
                p_up = glauber.prob_up(beta * h)
                u = jax.random.uniform(keys[c], s.shape)
                proposal = jnp.where(u < p_up, 1.0, -1.0).astype(s.dtype)
                s = jnp.where(masks[c], proposal, s)
        return KernelState(s=s, t=state.t + 1.0 / self.lambda0, e=None, aux=())


# run(unroll="auto") on the compiled dense tau-leap kernel: steps per scan
# iteration. A step is then about five device operations (draw, kernel,
# first-hit energy), the scan's own per-iteration work paid once a block;
# one step per iteration took 13 (TPU v5e, SK n=2048, 128 chains), and a
# profiler trace, which keeps about 5M of them, then lost most of a 45 s
# window.
TAU_LEAP_BLOCK_STEPS = 32


@register_kernel("tau_leap")
@partial(
    jax.tree_util.register_dataclass,
    data_fields=("trim",),
    meta_fields=("dt", "lambda0", "backend"),
)
@dataclasses.dataclass(frozen=True)
class TauLeap:
    """The PASS asynchronous model: every neuron flips independently with
    prob 1-exp(-dt*lambda_i) per step of model time dt (in units of
    1/lambda0). Small dt*lambda0 -> exact CTMC; large dt -> 'stale neighbor'
    distortion, the TPU analogue of the chip's circuit-delay skew (Fig S9).

    Works on LatticeIsing (stencil fields, clamp/dead masks), DenseIsing,
    and SparseIsing (gathered neighbor fields via `local_fields`).
    The dense form supports `backend="pallas"`: weights are int8-quantized
    once at init and every step runs the fused Pallas `tau_leap_step` kernel
    (MXU matmul -> flip epilogue; compiled on TPU, interpreted elsewhere)."""

    backends = ("ref", "pallas")
    problem_kinds = ("dense", "lattice", "sparse")

    dt: float = 0.1
    lambda0: float = 1.0
    backend: str = "ref"  # "ref" | "pallas"
    trim: Optional[glauber.SigmoidTrim] = None

    def backends_for(self, problem) -> tuple[str, ...]:
        # lattice/sparse tau-leap have no Pallas kernel; trims are ref-only
        """Backends valid for this kernel/problem pair."""
        if isinstance(problem, (LatticeIsing, SparseIsing)) or self.trim is not None:
            return ("ref",)
        return self.backends

    def preferred_unroll(self, problem) -> int:
        """Steps per scan iteration for run(unroll="auto"): a block of
        `TAU_LEAP_BLOCK_STEPS` where every step is a call of the compiled
        dense kernel (backend "pallas" on a TPU), 1 elsewhere."""
        from repro.kernels import ops

        if self.backend == "pallas" and isinstance(problem, DenseIsing) and ops.on_tpu():
            return TAU_LEAP_BLOCK_STEPS
        return 1

    def init(self, problem, key, s0=None, faults=None) -> KernelState:
        """Initial state (int8-quantized weights under pallas)."""
        if s0 is None:
            s0 = random_init(key, state_shape(problem))
        if faults is not None:
            s0 = faults.apply_stuck(s0)
        aux = ()
        if isinstance(problem, LatticeIsing):
            if self.backend == "pallas":
                raise NotImplementedError(
                    "pallas tau-leap supports dense problems only; the lattice "
                    "form has no Pallas kernel (use chromatic_gibbs for the "
                    "fused lattice sweep)"
                )
            s0 = problem.apply_clamps(s0)
        elif isinstance(problem, SparseIsing):
            if self.backend == "pallas":
                raise NotImplementedError(
                    "pallas tau-leap supports dense problems only; the sparse "
                    "form has no Pallas kernel (use colored_gibbs for the "
                    "fused sparse sweep)"
                )
        elif self.backend == "pallas":
            if self.trim is not None:
                raise NotImplementedError("pallas tau-leap does not support trims")
            from repro.kernels import ops

            aux = ops.quantize_dense(problem.J)  # (j_i8, scale), once per run
        return KernelState(s=s0, t=jnp.asarray(0.0, jnp.float32), e=None, aux=aux)

    def step(self, problem, state, key, beta, faults=None) -> KernelState:
        """One tau-leap of model time dt: independent thinned flips.

        Field noise perturbs the pre-beta field (h -> h + eta on the ref
        paths; bias operand b + eta on the fused Pallas path). Stuck and
        dropped sites keep their spin: the ref paths freeze/filter the
        flips, the Pallas path warps their uniform to 1.0 (p_flip < 1
        always, so u = 1.0 can never flip) — the kernel itself is fault-
        oblivious. Lattice stuck sites arrive pre-absorbed into the clamp
        masks via `FaultModel.bind`."""
        s = state.s
        eta = keep = None
        if faults is not None and (faults.noisy or faults.drops):
            key, k_noise, k_drop = jax.random.split(key, 3)
            if faults.noisy:
                eta = faults.field_noise(k_noise, s.shape)
            if faults.drops:
                keep = faults.keep_mask(k_drop, s.shape)
        stuck = faults.stuck_flat() if faults is not None else None
        if isinstance(problem, LatticeIsing):
            h = problem.local_fields(s)
            if eta is not None:
                h = h + eta
            s = _tau_leap_flip(
                s, beta * h, key, self.dt, self.trim, problem.frozen_mask, keep
            )
            s = problem.apply_clamps(s)
        elif self.backend == "pallas":
            from repro.kernels import ops

            j_i8, scale = state.aux
            u = jax.random.uniform(key, s.shape)
            if stuck is not None or keep is not None:
                block = (
                    stuck if keep is None
                    else (~keep if stuck is None else stuck | ~keep)
                )
                u = jnp.where(block, 1.0, u)
            # beta scales the field: h_beta = acc*(beta*scale) + beta*b
            s = ops.tau_leap_step(
                s[None, :],
                j_i8,
                beta * problem.b if eta is None else beta * (problem.b + eta),
                beta * scale,
                u[None, :],
                jnp.asarray(self.dt, jnp.float32),
                mode="kernel",
            )[0]
        else:
            h = problem.local_fields(s)
            if eta is not None:
                h = h + eta
            s = _tau_leap_flip(s, beta * h, key, self.dt, self.trim, stuck, keep)
        return KernelState(
            s=s, t=state.t + self.dt / self.lambda0, e=None, aux=state.aux
        )


# Total-rate floor for the CTMC: below this the chain is treated as frozen
# (the dwell time is clamped to ~1e30 and no flip is performed). Shared by
# the denominator clamp and the aliveness test; above it the dwell time and
# the site draw (exact-log categorical or sum-tree descent) are both
# unclamped and exact.
RATE_FLOOR = 1e-30

# site_draw="auto" switches to the sum-tree draw at this problem size. The
# tree wins on CPU at every measured size (its draw needs ONE uniform vs one
# Gumbel per site), but below this the scan draw is already cheap and "auto"
# keeps the historical random stream that small-scale statistical tests and
# the legacy gillespie() wrappers pinned.
TREE_SITE_DRAW_MIN_N = 64

# Event-block size "auto" unrolling picks for the tree path on big problems
# (see CTMC.preferred_unroll).
CTMC_TREE_BLOCK_EVENTS = 2
CTMC_TREE_BLOCK_MIN_N = 512


@register_kernel("ctmc")
@partial(
    jax.tree_util.register_dataclass,
    data_fields=(),
    meta_fields=("lambda0", "site_draw"),
)
@dataclasses.dataclass(frozen=True)
class CTMC:
    """Exact event-driven continuous-time Glauber dynamics (Gillespie/SSA).
    One step = one flip event: Exp(sum_i lambda_i) waiting time, site drawn
    proportionally to lambda_i = lambda0 * sigma(2 beta h_i s_i). The
    embedded chain is statistically exact — the fidelity reference for the
    tau-leap kernel and the hardware. Incremental fields: O(n) per event.

    site_draw selects the event-selection mechanism (statistically
    identical laws, different random streams):

      "scan" — `jax.random.categorical` over log(rates): one Gumbel per
          site per event, O(n) random bits. The historical path.
      "tree" — `event_tree` sum-tree: the draw costs ONE uniform and an
          O(log n) descent. aux carries (h, tree) where the tree is, by
          definition, the rate tree the state's MOST RECENT event was drawn
          from (pre-flip rates at that event's beta) in its flat
          Pallas-ready layout. For DENSE problems step() rebuilds before
          every draw (every rate changes per event and a scheduled beta
          rescales every leaf): one fused O(n) build, no per-site
          randomness — the expensive part of "scan".
      "auto" — "tree" for n >= TREE_SITE_DRAW_MIN_N else "scan".

    SPARSE problems (SparseIsing) make the tree path incremental: a flip at
    site i changes only the rates of i and its <= max_deg neighbors, so the
    carried tree is repaired in place via `event_tree.update_many` —
    O(max_deg * log n) per event instead of the dense O(n) rebuild. aux
    carries (h, tree, tree_beta); the tree always holds the CURRENT state's
    rates at tree_beta, and a step whose beta differs (annealed schedules
    change beta every event) pays one O(n) rebuild before drawing. The
    O(deg) win therefore shows on constant-beta runs; note that with
    n_chains > 1 the rebuild-vs-reuse `lax.cond` is batched by vmap into a
    select that evaluates both branches, so peak sparse throughput is a
    single-chain (or pmap-sharded) story.
    """

    problem_kinds = ("dense", "sparse")

    lambda0: float = 1.0
    site_draw: str = "auto"  # "scan" | "tree" | "auto"

    def resolved_site_draw(self, problem) -> str:
        """The concrete draw mechanism for this problem size (static)."""
        if self.site_draw not in ("scan", "tree", "auto"):
            raise ValueError(
                f"site_draw must be 'scan' | 'tree' | 'auto', got {self.site_draw!r}"
            )
        if self.site_draw == "auto":
            return "tree" if problem.n >= TREE_SITE_DRAW_MIN_N else "scan"
        return self.site_draw

    def preferred_unroll(self, problem) -> int:
        """Event-block size for run(unroll="auto"): amortize the scan body
        over a few events on problems big enough that per-event overhead
        shows; 1 elsewhere (small problems lose to the larger program)."""
        if (
            self.resolved_site_draw(problem) == "tree"
            and problem.n >= CTMC_TREE_BLOCK_MIN_N
        ):
            return CTMC_TREE_BLOCK_EVENTS
        return 1

    def init(self, problem, key, s0=None, faults=None) -> KernelState:
        """Initial state with fields (and the rate tree on the tree path).

        Stuck sites are forced to their stuck values and their rates masked
        to zero BEFORE the tree is built, so the carried tree's invariant
        (it holds exactly the rates events are drawn from) survives faults
        — tree-vs-scan parity is a property of the masked rate table."""
        if s0 is None:
            s0 = random_init(key, state_shape(problem))
        if faults is not None:
            s0 = faults.apply_stuck(s0)
        h = problem.local_fields(s0)
        if self.resolved_site_draw(problem) == "tree":
            # Tree at beta=1: fixes the aux pytree structure (see the class
            # docstring for the carried tree's exact meaning). Dense step()
            # rebuilds at the step's actual beta before every draw; the
            # sparse step carries tree_beta and rebuilds only on change.
            rates = self.lambda0 * glauber.flip_prob(h, s0)
            stuck = faults.stuck_flat() if faults is not None else None
            if stuck is not None:
                rates = jnp.where(stuck, 0.0, rates)
            tree = event_tree.build(rates)
            if isinstance(problem, SparseIsing):
                aux = (h, tree, jnp.asarray(1.0, jnp.float32))
            else:
                aux = (h, tree)
        else:
            aux = h
        return KernelState(
            s=s0, t=jnp.asarray(0.0, jnp.float32), e=problem.energy(s0), aux=aux
        )

    def step(self, problem, state, key, beta, faults=None) -> KernelState:
        """One Gillespie event: dwell time + proportional site draw.

        Faults perturb the RATE TABLE the event is drawn from — noise on
        the fields, zero rates at stuck sites — before the tree build /
        categorical, so both draw paths stay exact samplers of the faulted
        rates. A dropped event still advances model time (the device
        waited; the flip was lost). The carried h and the incremental
        energy always track the TRUE fields of the actual state."""
        tree_draw = self.resolved_site_draw(problem) == "tree"
        if tree_draw and isinstance(problem, SparseIsing):
            return self._sparse_tree_step(problem, state, key, beta, faults)
        s = state.s
        h = state.aux[0] if tree_draw else state.aux
        if faults is not None and (faults.noisy or faults.drops):
            key, k_noise, k_drop = jax.random.split(key, 3)
        k_dt, k_site = jax.random.split(key)
        h_eff = h
        if faults is not None and faults.noisy:
            h_eff = h + faults.field_noise(k_noise, h.shape)
        rates = self.lambda0 * glauber.flip_prob(beta * h_eff, s)
        stuck = faults.stuck_flat() if faults is not None else None
        if stuck is not None:
            rates = jnp.where(stuck, 0.0, rates)
        # At large beta every sigma(2 beta h_i s_i) underflows toward 0 in a
        # frozen cold chain. Dividing by the raw sum would give dt=inf (NaN
        # model time), so clamp the denominator and suppress the flip below
        # RATE_FLOOR — identically on both draw paths.
        if tree_draw:
            # Rates depend on beta through the sigmoid, so a scheduled beta
            # invalidates every leaf: rebuild at the step's beta (for dense
            # couplings all n fields change per event anyway — the O(deg)
            # event_tree.update_many path is the sparse step below).
            # Zero-total trees degenerate to the last leaf; the rounding
            # clamp to n-1 also covers it, and `alive` then discards the
            # flip.
            tree = event_tree.build(rates)
            total = event_tree.total(tree)
            i = jnp.minimum(
                event_tree.descend(tree, jax.random.uniform(k_site)), problem.n - 1
            )
        else:
            # log(rates) without an additive floor keeps the site draw
            # exactly proportional however small the rates get (log(0) is
            # -inf = zero probability; an additive floor would flip a near-
            # uniformly random site once rates drop near it); all-zero rates
            # degenerate to site 0, which `alive` then discards.
            total = jnp.sum(rates)
            i = jax.random.categorical(k_site, jnp.log(rates))
        alive = total > RATE_FLOOR
        if faults is not None and faults.drops:
            alive = alive & (jax.random.uniform(k_drop, ()) >= faults.dropout)
        dt = jax.random.exponential(k_dt) / jnp.maximum(total, RATE_FLOOR)
        delta = jnp.where(alive, -2.0 * s[i], 0.0)
        e = state.e + delta * h[i]
        h = _apply_field_delta(problem, h, i, delta)
        s = s.at[i].add(delta)
        aux = (h, tree) if tree_draw else h
        return KernelState(s=s, t=state.t + dt, e=e, aux=aux)

    def _sparse_tree_step(
        self, problem: SparseIsing, state, key, beta, faults=None
    ) -> KernelState:
        """One event with O(max_deg * log n) tree maintenance.

        The carried tree holds the CURRENT state's rates at tree_beta, so
        when beta is unchanged the draw reuses it as-is; a beta change
        rescales every leaf through the sigmoid and pays one O(n) rebuild
        (every event, under annealed schedules — the O(deg) path needs a
        constant beta to shine). After the flip, only site i and its real
        neighbors changed rate: scatter-add their leaf deltas over the
        root paths in one `update_many`, with padded slots masked to zero
        delta (their index aliases a live leaf, so a degree mask — not the
        padding weights — keeps them inert here).

        Faults: stuck rates are masked to zero wherever rates are computed
        (build and repair), so the tree invariant holds for the masked
        table. Field noise redraws EVERY leaf each event, so the
        incremental path degrades to a per-event O(n) rebuild — the repair
        has nothing to reuse — and the carried tree is left stale (the
        next event rebuilds before drawing anyway). Dropout discards the
        flip but keeps the dwell time."""
        s = state.s
        h, tree, tree_beta = state.aux
        noisy = faults is not None and faults.noisy
        if faults is not None and (noisy or faults.drops):
            key, k_noise, k_drop = jax.random.split(key, 3)
        k_dt, k_site = jax.random.split(key)
        stuck = faults.stuck_flat() if faults is not None else None

        def masked(rates):
            """Zero the stuck sites' rates (no-op without a stuck mask)."""
            return rates if stuck is None else jnp.where(stuck, 0.0, rates)

        if noisy:
            eta = faults.field_noise(k_noise, h.shape)
            draw_tree = event_tree.build(
                masked(self.lambda0 * glauber.flip_prob(beta * (h + eta), s))
            )
        else:
            draw_tree = jax.lax.cond(
                beta == tree_beta,
                lambda t: t,
                lambda t: event_tree.build(
                    masked(self.lambda0 * glauber.flip_prob(beta * h, s))
                ),
                tree,
            )
        total = event_tree.total(draw_tree)
        i = jnp.minimum(
            event_tree.descend(draw_tree, jax.random.uniform(k_site)), problem.n - 1
        )
        alive = total > RATE_FLOOR
        if faults is not None and faults.drops:
            alive = alive & (jax.random.uniform(k_drop, ()) >= faults.dropout)
        dt = jax.random.exponential(k_dt) / jnp.maximum(total, RATE_FLOOR)
        delta = jnp.where(alive, -2.0 * s[i], 0.0)
        e = state.e + delta * h[i]
        nbr = problem.nbr_idx[i]  # (max_deg,) — padded slots point at i
        h = h.at[nbr].add(problem.nbr_w[i] * delta)  # zero at padded slots
        s = s.at[i].add(delta)
        if noisy:
            # Fresh noise invalidates every leaf next event: skip the
            # repair, carry the stale tree (same pytree structure).
            return KernelState(
                s=s, t=state.t + dt, e=e,
                aux=(h, draw_tree, jnp.asarray(beta, jnp.float32)),
            )
        affected = jnp.concatenate([i[None], nbr])
        live = jnp.concatenate(
            [jnp.ones((1,), bool), jnp.arange(problem.max_deg) < problem.deg[i]]
        )
        new_rates = self.lambda0 * glauber.flip_prob(
            beta * h[affected], s[affected]
        )
        if stuck is not None:
            new_rates = jnp.where(stuck[affected], 0.0, new_rates)
        leaf_delta = jnp.where(
            live, new_rates - event_tree.leaves_at(draw_tree, affected), 0.0
        )
        tree = event_tree.update_many(draw_tree, affected, leaf_delta)
        return KernelState(
            s=s, t=state.t + dt, e=e, aux=(h, tree, jnp.asarray(beta, jnp.float32))
        )


# ---------------------------------------------------------------------------
# The driver
# ---------------------------------------------------------------------------


class RunTiming(NamedTuple):
    """Host-side wall-clock accounting for one `run(..., timeit=True)` call.

    compile_s:         first-call overhead (trace + compile), estimated as
                       first_call_wall - steady_state_wall, floored at 0.
    wall_s:            steady-state wall time of one full driver call.
    steps_per_s:       n_steps / wall_s (per chain).
    chain_steps_per_s: n_steps * n_chains / wall_s — the throughput figure
                       benchmarks gate on.
    """

    compile_s: float
    wall_s: float
    steps_per_s: float
    chain_steps_per_s: float


class RunResult(NamedTuple):
    """Result of a `run()` call. With n_chains > 1 every field gains a
    leading chain dimension.

    s:        final state.
    t:        final model time (seconds of chip time).
    samples:  (n_samples, ...) states recorded every `sample_every` steps
              (empty leading dim when sample_every == 0).
    times:    (n_samples,) model time at each recorded state.
    energies: (n_samples,) energy at each recorded state.
    t_hit:    first model time with energy <= first_hit (inf if never);
              None when first_hit was not requested.
    hit:      whether the target was reached; None when not requested.
    timing:   RunTiming when run(..., timeit=True); None otherwise.
    diagnostics: RunDiagnostics when run(..., diagnostics=True) — per-chain
              flip counters, Welford energy mean/variance, and first-hit
              step index collected inside the scan (see
              `repro.core.diagnostics`); None otherwise.
    """

    s: jax.Array
    t: jax.Array
    samples: jax.Array
    times: jax.Array
    energies: jax.Array
    t_hit: Any = None
    hit: Any = None
    timing: Any = None
    diagnostics: Any = None


def kernel_backends(kernel, problem=None) -> tuple[str, ...]:
    """Backends a kernel can actually execute ("ref" always works).

    Kernels whose support depends on their own config (trims are ref-only)
    or on the problem class (tau-leap: Pallas kernel for dense only) narrow
    the answer via an optional `backends_for(problem)` method; it must
    accept problem=None, answering for the kernel config alone.
    """
    fn = getattr(kernel, "backends_for", None)
    if fn is not None:
        return fn(problem)
    return getattr(type(kernel), "backends", ("ref",))


def _resolve_backend(backend: Optional[str], kernel=None, problem=None) -> Optional[str]:
    """Resolve a requested backend against what `kernel` supports.

    An explicit "pallas" request on a kernel with no Pallas path raises
    ValueError — it used to silently run the ref path, which turned every
    backend benchmark/test into a potential no-op. "auto" picks the best
    backend the kernel supports on this platform (so it stays usable for
    ref-only kernels).
    """
    if backend is None:
        return None
    if backend not in ("ref", "pallas", "auto"):
        raise ValueError(f"backend must be 'ref' | 'pallas' | 'auto', got {backend!r}")
    supported = ("ref", "pallas") if kernel is None else kernel_backends(kernel, problem)
    if backend == "auto":
        from repro.kernels import ops

        return "pallas" if ops.on_tpu() and "pallas" in supported else "ref"
    if backend not in supported:
        name = getattr(kernel, "name", type(kernel).__name__)
        raise ValueError(
            f"kernel {name!r} does not support backend {backend!r}; "
            f"supported backends: {supported}"
        )
    return backend


def _run_core(
    problem, kernel, key, s0, betas, e_target, *,
    n_steps, sample_every, track_hit, unroll=1, diagnostics=False, faults=None,
):
    """Single-chain scan: the one loop every sampler entry point shares.

    `unroll` is the event-block size: each `lax.scan` iteration runs that
    many kernel steps back to back (lax.scan body unrolling), amortizing
    per-iteration loop overhead without changing a single drawn number —
    keys and betas are pre-split per step either way, so results are
    bit-identical for every unroll.

    `diagnostics` (static) threads a `diag.DiagAcc` through the carry —
    per-step flip counts, Welford energy moments, first-hit step. Keys and
    betas are pre-split identically either way and the False branch builds
    the exact pre-diagnostics program, so turning it off costs nothing and
    changes nothing; turning it on changes only what is RECORDED (kernels
    without an incremental energy pay one problem.energy per step).

    `faults` is a residual `FaultModel` (already `bind()`-applied by
    `run()`) or None. When None, kernels are called with the SAME 4-arg
    signatures as before this parameter existed — the fault-free program
    is byte-identical for any kernel, including user kernels that never
    heard of faults."""
    if s0 is None:
        key, k_init = jax.random.split(key)
    else:
        k_init = None
    if faults is None:
        state = kernel.init(problem, k_init, s0)
    else:
        state = kernel.init(problem, k_init, s0, faults)
    keys = jax.random.split(key, n_steps)

    e0 = state.e if state.e is not None else problem.energy(state.s)
    init_hit = (e0 <= e_target) & jnp.asarray(track_hit)
    t_hit0 = jnp.where(init_hit, 0.0, jnp.inf)

    def step_fn(carry, inp):
        """One scan iteration: kernel step + hit/diagnostics tracking."""
        if diagnostics:
            st, t_hit, hit, acc = carry
        else:
            st, t_hit, hit = carry
        k, beta = inp
        if faults is None:
            st_new = kernel.step(problem, st, k, beta)
        else:
            st_new = kernel.step(problem, st, k, beta, faults)
        e = new_hit = None
        if track_hit or diagnostics:
            e = st_new.e if st_new.e is not None else problem.energy(st_new.s)
        if track_hit:
            new_hit = (e <= e_target) & (~hit)
            t_hit = jnp.where(new_hit, st_new.t, t_hit)
            hit = hit | new_hit
        if diagnostics:
            n_flipped = jnp.sum(st_new.s != st.s).astype(jnp.int32)
            acc = diag.acc_update(acc, n_flipped, e, new_hit)
            return (st_new, t_hit, hit, acc), None
        return (st_new, t_hit, hit), None

    if diagnostics:
        carry = (state, t_hit0, init_hit,
                 diag.acc_init(e0, init_hit if track_hit else None))
    else:
        carry = (state, t_hit0, init_hit)

    track_e = state.e is not None  # static: kernels maintain e incrementally or never
    inner = lambda carry, xs, length: jax.lax.scan(
        step_fn, carry, xs, unroll=max(1, min(unroll, length))
    )
    if sample_every > 0:
        n_samples = n_steps // sample_every
        m = n_samples * sample_every
        blk = lambda x: x[:m].reshape((n_samples, sample_every) + x.shape[1:])

        def block(carry, inp):
            """One observation block: sample_every steps then record."""
            carry, _ = inner(carry, inp, sample_every)
            st = carry[0]
            return carry, (st.s, st.t, st.e if track_e else ())

        carry, (samples, times, energies) = jax.lax.scan(
            block, carry, (blk(keys), blk(betas))
        )
        if m < n_steps:  # remainder steps after the last observation
            carry, _ = inner(carry, (keys[m:], betas[m:]), n_steps - m)
        if not track_e:
            energies = jax.vmap(problem.energy)(samples)
    else:
        carry, _ = inner(carry, (keys, betas), n_steps)
        st = carry[0]
        samples = jnp.zeros((0,) + st.s.shape, st.s.dtype)
        times = jnp.zeros((0,), jnp.float32)
        # e0 has the energy dtype both recording branches produce (st.e or
        # problem.energy) — NOT the state dtype, which silently diverged
        # from the sampling branches' float32 energies.
        energies = jnp.zeros((0,), e0.dtype)

    if diagnostics:
        state, t_hit, hit, acc = carry
        run_diag = diag.acc_finalize(acc, n_sites=int(state.s.size))
    else:
        state, t_hit, hit = carry
        run_diag = None
    return RunResult(
        s=state.s,
        t=state.t,
        samples=samples,
        times=times,
        energies=energies,
        t_hit=t_hit if track_hit else None,
        hit=hit if track_hit else None,
        diagnostics=run_diag,
    )


@partial(
    jax.jit,
    static_argnames=("n_steps", "sample_every", "track_hit", "unroll", "diagnostics"),
)
def _run_single(
    problem, kernel, key, s0, betas, e_target, n_steps, sample_every, track_hit,
    unroll, diagnostics, faults,
):
    return _run_core(
        problem, kernel, key, s0, betas, e_target,
        n_steps=n_steps, sample_every=sample_every, track_hit=track_hit, unroll=unroll,
        diagnostics=diagnostics, faults=faults,
    )


@partial(
    jax.jit,
    static_argnames=(
        "n_steps", "sample_every", "track_hit", "n_chains", "unroll", "diagnostics"
    ),
)
def _run_batched(
    problem, kernel, keys, s0, betas, e_target, n_steps, sample_every, track_hit,
    n_chains, unroll, diagnostics, faults,
):
    def one(key, s0_c, betas_c):
        """One chain's full scan (vmapped over chains; `faults` — like
        `problem` — is chain-invariant, so it rides in as a closure
        constant rather than a mapped axis)."""
        return _run_core(
            problem, kernel, key, s0_c, betas_c, e_target,
            n_steps=n_steps, sample_every=sample_every, track_hit=track_hit,
            unroll=unroll, diagnostics=diagnostics, faults=faults,
        )

    in_axes = (0, None if s0 is None else 0, 0 if betas.ndim == 2 else None)
    return jax.vmap(one, in_axes=in_axes)(keys, s0, betas)


def _resolve_unroll(unroll, kernel, problem) -> int:
    """Resolve the event-block size: "auto" asks the kernel (CTMC blocks
    events on big problems), an int is validated and used as-is."""
    if unroll == "auto":
        fn = getattr(kernel, "preferred_unroll", None)
        return fn(problem) if fn is not None else 1
    if not isinstance(unroll, int) or isinstance(unroll, bool) or unroll < 1:
        raise ValueError(f"unroll must be 'auto' or an int >= 1, got {unroll!r}")
    return unroll


def run(
    problem,
    kernel: Union[SamplerKernel, str],
    key: jax.Array,
    *,
    n_steps: int,
    s0: Optional[jax.Array] = None,
    schedule: ScheduleLike = None,
    n_chains: int = 1,
    sample_every: int = 0,
    first_hit: Optional[Any] = None,
    backend: Optional[str] = None,
    unroll: Union[int, str] = "auto",
    timeit: bool = False,
    diagnostics: bool = False,
    faults: Optional[FaultModel] = None,
) -> RunResult:
    """Run `n_steps` of `kernel` on `problem` — the single sampling driver.

    Args:
      problem: DenseIsing, LatticeIsing, or SparseIsing. The kernel must
        declare support for the problem's kind (`problem_kinds`) — an
        unsupported pairing (e.g. chromatic_gibbs on a sparse graph) raises
        ValueError naming both, instead of a shape error inside the scan.
      kernel: a SamplerKernel instance, or a registered kernel name.
      key: PRNG key; split into one key per step (and per chain).
      n_steps: kernel steps (sweeps for chromatic, events for ctmc).
      s0: optional initial state — (n_chains, ...) when n_chains > 1;
        random ±1 init per chain when omitted.
      schedule: beta schedule — None (beta=1), float, Schedule object,
        (n_steps,) array, or (n_chains, n_steps) per-chain array.
      n_chains: independent chains batched via vmap with per-chain keys.
      sample_every: observation stride (the chip's FPGA-side observer clock);
        0 records nothing.
      first_hit: energy target — tracks (t_hit, hit) per chain.
      backend: "ref" | "pallas" | "auto" — overrides the kernel's backend
        field where it has one (dense tau-leap and chromatic gibbs route
        through their fused Pallas kernels under "pallas"; "auto" compiles
        on TPU, refs elsewhere). Requesting "pallas" on a kernel or
        kernel/problem combination without Pallas support raises ValueError
        — no silent ref fallback.
      unroll: event-block size — how many kernel steps each `lax.scan`
        iteration runs back to back, amortizing per-iteration loop overhead
        (the per-event cost that dominates small CTMC problems). Results
        are bit-identical for every unroll (keys/betas are pre-split per
        step). "auto" asks the kernel (`preferred_unroll(problem)`; CTMC
        blocks events on big tree-draw problems, everything else stays 1).
      timeit: measure wall-clock throughput — the call runs twice (compile
        pass then steady-state pass, identical results: same key) and the
        result carries a `RunTiming` in `.timing`. One-shot convenience;
        the benchmark harness times whole `run()` calls itself with median
        repeats (`benchmarks.runner`). Off by default.
      diagnostics: collect in-scan run diagnostics (per-chain flip
        counters, Welford energy mean/variance, first-hit step index) into
        `RunResult.diagnostics` as a `RunDiagnostics` — see
        `repro.core.diagnostics`. Sampled values are bit-identical with or
        without it (keys and betas are pre-split per step either way);
        False (the default) compiles the exact pre-diagnostics program.
        Kernels without an incremental energy (tau_leap, the Gibbs sweeps)
        pay one `problem.energy` per step while it is on.
      faults: optional `repro.core.faults.FaultModel` — simulate device
        non-idealities (stuck spins, b-bit coupling quantization, field
        noise, update dropout; see that module for per-kernel semantics).
        Validated host-side, then `bind()` is applied once: quantization
        rewrites the couplings, lattice stuck masks are absorbed into the
        clamp epilogue, and only the residual dynamic faults reach the
        kernels. None (the default) compiles the exact fault-free program
        — results are bit-identical to a run that never passed the
        argument, for every kernel and backend.

    Each call that runs (is not traced by an outer `jax.jit`) opens the
    host spans `run`, `run.validate`, `run.prep` and `run.call` and leaves
    one record of their durations: see `repro.core.tracing`. They touch
    only the host.
    """
    with tracing.call() as span:
        with span("run.validate"):
            if isinstance(kernel, str):
                kernel = get_kernel(kernel)
            check_problem_kind(kernel, problem)
            resolved = _resolve_backend(backend, kernel, problem)
            if resolved is not None and hasattr(kernel, "backend") and kernel.backend != resolved:
                kernel = dataclasses.replace(kernel, backend=resolved)

            if faults is not None:
                faults.validate(problem)
                problem, faults = faults.bind(problem)
            # Fail loudly on a problem whose couplings/biases cannot produce
            # finite energies (NaN/Inf snuck past construction, or an
            # over-aggressive fault model) — otherwise every recorded energy
            # is NaN and the TTS fits in `observables.fit_scaling` silently
            # degrade. The probe is a host-side check: when run() is itself
            # being traced (e.g. inside the jitted tempering loop) the energy
            # is a tracer and the check is skipped — concreteness is gone,
            # and the caller's own entry into jit already went through an
            # un-traced run() or can probe explicitly.
            e_probe = problem.energy(jnp.ones(state_shape(problem)))
            if not isinstance(e_probe, jax.core.Tracer) and not bool(jnp.isfinite(e_probe)):
                raise NonFiniteEnergyError(
                    f"problem energy is non-finite (probe energy {float(e_probe)}); "
                    "check the couplings/biases (and any FaultModel) for NaN/Inf"
                )

        with span("run.prep"):
            betas = resolve_schedule(schedule, n_steps, n_chains)
            track_hit = first_hit is not None
            e_target = jnp.asarray(first_hit if track_hit else jnp.inf, jnp.float32)
            unroll = _resolve_unroll(unroll, kernel, problem)

            if n_chains == 1:
                call = lambda: _run_single(
                    problem, kernel, key, s0, betas, e_target, n_steps, sample_every,
                    track_hit, unroll, diagnostics, faults,
                )
            else:
                keys = jax.random.split(key, n_chains)
                call = lambda: _run_batched(
                    problem, kernel, keys, s0, betas, e_target, n_steps, sample_every,
                    track_hit, n_chains, unroll, diagnostics, faults,
                )

        with span("run.call"):
            if not timeit:
                return call()

            t0 = time.perf_counter()
            jax.block_until_ready(call())
            first_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            res = jax.block_until_ready(call())
            wall_s = max(time.perf_counter() - t0, 1e-9)
            timing = RunTiming(
                compile_s=max(0.0, first_s - wall_s),
                wall_s=wall_s,
                steps_per_s=n_steps / wall_s,
                chain_steps_per_s=n_steps * n_chains / wall_s,
            )
            return res._replace(timing=timing)
