"""Pallas TPU kernels for the PASS hot loops.

Each kernel <name>.py carries a pl.pallas_call with explicit BlockSpec VMEM
tiling; chains.py is the one rule by which `run()`'s chains fold into a
kernel call; ops.py is the door `repro.core` calls them through; ref.py is
the pure-jnp oracle every kernel is tested against (interpret=True sweeps).
"""
from repro.kernels import ops, ref  # noqa: F401
