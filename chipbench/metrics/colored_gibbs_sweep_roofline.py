"""Share (%) of its roofline that the sparse colored Gibbs kernel reaches:
the least time of the window's sweeps (`dynamics/colored_gibbs.work` over
the chip's peaks) over the kernel's time in the trace."""


def read(ctx):
    """`Context.roofline` of `colored_gibbs_sweep`, %."""
    return ctx.roofline("colored_gibbs_sweep")
