"""Calls of the sparse colored Gibbs kernel per sweep in the program that
ran, as the program notes it while tracing (`repro.core.tracing`): one per
chain where chains are mapped as a grid axis, one where the chains share a
call in the kernel's lanes. A program without the note reads as nothing."""


def read(ctx):
    """`tracing.row_occupancy("colored_gibbs_sweep").calls`, or None."""
    try:
        from repro.core import tracing
    except ImportError:
        return None
    rows = getattr(tracing, "row_occupancy", lambda kernel: None)("colored_gibbs_sweep")
    return None if rows is None else float(rows.calls)
