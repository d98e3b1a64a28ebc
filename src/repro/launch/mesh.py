"""Production mesh factory.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — callers decide when devices are realized.

Single pod : (16, 16)      axes ("data", "model")   — 256 chips (v5e pod)
Multi-pod  : (2, 16, 16)   axes ("pod", "data", "model") — 512 chips;
             the "pod" axis crosses DCN and carries only data-parallel
             gradient reduction (optionally int8-compressed).
"""
from __future__ import annotations

import jax

from repro.sharding.partition import make_auto_mesh


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")) -> jax.sharding.Mesh:
    """Small mesh over however many devices exist (tests/CI)."""
    return make_auto_mesh(shape, axes)
