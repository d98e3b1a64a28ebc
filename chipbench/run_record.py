"""The program's own record of its `run()` calls (`repro.core.tracing`), as
the per-layer metrics of `run()`'s host path read it.

Each job of a window is one `run()` call, so the window's calls are the
last `jobs` records. A window of more calls than the record keeps
(`tracing.KEEP`) is read from its last `KEEP` calls. A program without the
record (one older than `repro.core.tracing`), or a record that holds fewer
calls than that, reads as nothing.

These spans time `run()` from inside the program. The harness's own
`dispatch` span around the call (`run_dispatch_ms`) judges a gain on the
host path; `tests/chipbench/test_run_record.py` pins what each span covers.
"""
from __future__ import annotations

import numpy as np


def window_calls(ctx):
    """The records of the window's `run()` calls (its last `tracing.KEEP`
    at most), oldest first, or None."""
    try:
        from repro.core import tracing
    except ImportError:
        return None
    want = min(ctx.window.jobs, tracing.KEEP)
    calls = tracing.recent(want)
    return calls if want > 0 and len(calls) == want else None


def mean_span_ms(ctx, field: str):
    """Mean over the window's calls of the span duration `field`, ms."""
    calls = window_calls(ctx)
    if calls is None:
        return None
    return 1e-6 * float(np.mean([getattr(c, field) for c in calls]))
