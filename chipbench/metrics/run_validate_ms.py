"""Host time of `run()`'s checks per job, from the program's `run.validate`
span: kernel lookup, problem-kind and backend checks, fault binding, and
the finite-energy probe with its read to the host. Mean over the window's
calls, ms."""
from chipbench.run_record import mean_span_ms


def read(ctx):
    """Mean `run.validate` span of the window's calls, ms."""
    return mean_span_ms(ctx, "validate_ns")
