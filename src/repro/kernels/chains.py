"""The one rule by which `run()`'s chains reach a Pallas kernel.

`run()` steps each chain as a one-chain batch (`s[None]`) and maps chains
with `jax.vmap`. `batched` gives a kernel a `jax.vmap` rule: a kernel with
a `fold` takes the A mapped calls of B chains as ONE call of A*B chains
(its rows or lanes), reading its shared operands once per call; a kernel
without one, or a map over an operand the fold shares (never in `run()`),
takes vmap's default rule, one call per mapped element as a grid axis.
Each trace notes the kernel's chains per call, the rows they take padded
to the kernel's tile, and the calls per step in
`repro.core.tracing.row_occupancy`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import tracing


def batched(kernel, name: str, *, tile: int = 1, shared=(), fold=None):
    """`kernel(*operands, **options)` with the chains rule; operand 0 is the
    state, chains first.

    `shared` are the positions of the operands `fold` reads once for all
    chains. `fold(A, mapped, *operands)` returns the operands of one call
    of the A mapped calls' chains (`mapped` says which operand is mapped,
    as the vmap rule is given it), whose output's chains are A blocks of B.
    """

    def call(*args, **kw):
        def noted(*args, calls):
            rows = args[0].shape[0]
            tracing.note_rows(name, rows, -(-rows // tile) * tile, calls)
            return kernel(*args, **kw)

        @jax.custom_batching.custom_vmap
        def one(*args):
            return noted(*args, calls=1)

        @one.def_vmap
        def rule(A, in_batched, *args):
            if fold is None or any(jax.tree_util.tree_leaves([in_batched[i] for i in shared])):
                axes = jax.tree_util.tree_map(lambda m: 0 if m else None, list(in_batched))
                return jax.vmap(functools.partial(noted, calls=A), in_axes=axes)(*args), True
            out = noted(*fold(A, in_batched, *args), calls=1)
            return out.reshape((A, -1) + out.shape[1:]), True

        return one(*args)

    return call


def fold_operand(x: jax.Array, mapped: bool, A: int, shape, axis: int = 0) -> jax.Array:
    """`x` as the operand of one call of A*B chains, for a call whose
    operand has `shape` with its B chains on `axis`: the A calls' chains
    in order on that axis. A mapped `x` (the map's axis first) holds one
    value per mapped call or per chain; an unmapped one is the same for
    every mapped call."""
    lead, B, trail = tuple(shape[:axis]), shape[axis], tuple(shape[axis + 1:])
    if not mapped:
        x = jnp.broadcast_to(x, (A,) + x.shape)
    x = jnp.broadcast_to(x.reshape((A,) + lead + (-1,) + trail), (A,) + lead + (B,) + trail)
    return jnp.moveaxis(x, 0, axis).reshape(lead + (A * B,) + trail)
