"""Calls of the king's-lattice Gibbs kernel per sweep in the program that
ran, as the program notes it while tracing (`repro.core.tracing`): one per
chain where chains are mapped as a grid axis, fewer where chains share a
call. A program without the note reads as nothing."""


def read(ctx):
    """`tracing.row_occupancy("lattice_gibbs_sweep").calls`, or None."""
    try:
        from repro.core import tracing
    except ImportError:
        return None
    rows = getattr(tracing, "row_occupancy", lambda kernel: None)("lattice_gibbs_sweep")
    return None if rows is None else float(rows.calls)
