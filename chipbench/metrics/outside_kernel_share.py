"""Share (%) of the device's busy time spent outside the cell's Pallas
kernel: `run()`'s scan, the uniform draws, layout copies, the first-hit
energies."""


def read(ctx):
    """(busy - kernel) / busy from the trace, %."""
    t = ctx.trace
    if t is None or t.busy_s <= 0 or t.kernel_s <= 0:
        return None
    return 100.0 * (t.busy_s - t.kernel_s) / t.busy_s
