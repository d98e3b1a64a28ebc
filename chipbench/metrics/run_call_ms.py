"""Host time of the call of the jitted sampling program per job, from the
program's `run.call` span: mean over the window's calls, ms."""
from chipbench.run_record import mean_span_ms


def read(ctx):
    """Mean `run.call` span of the window's calls, ms."""
    return mean_span_ms(ctx, "call_ns")
