"""Share (%) of its roofline that the tiled king's-lattice Gibbs kernel
reaches: the least time of the window's sweeps
(`dynamics/chromatic_gibbs.work` over the chip's peaks) over the kernel's
time in the trace."""


def read(ctx):
    """`Context.roofline` of `lattice_gibbs_sweep`, %."""
    return ctx.roofline("lattice_gibbs_sweep")
