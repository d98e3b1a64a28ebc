"""Share (%) of the traced window in which the device ran no program:
1 - (union of program intervals) / window."""


def read(ctx):
    """Idle share from the trace, %."""
    t = ctx.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
