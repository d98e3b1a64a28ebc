"""Share (%) of its roofline that the dense tau-leap kernel reaches: the
least time of the window's steps (`dynamics/tau_leap.work` over the
chip's peaks) over the kernel's time in the trace."""


def read(ctx):
    """`Context.roofline` of `tau_leap_step`, %."""
    return ctx.roofline("tau_leap_step")
