"""Host time of `run()` per job: from the call until it hands back its
not-yet-ready result (the harness's `dispatch` span), mean in ms."""
import numpy as np


def read(ctx):
    """Mean dispatch span of the window's jobs, ms."""
    return 1e3 * float(np.mean(ctx.window.dispatch_s)) if ctx.window.dispatch_s else None
