"""95th percentile, over the window's jobs, of the time from calling
`run()` to the job's final states being on the host, ms."""
import numpy as np


def read(ctx):
    """p95 job latency, ms."""
    lat = ctx.window.latency_s
    return 1e3 * float(np.quantile(lat, 0.95)) if lat else None
