"""Load loop: the median time a query waits before the loop takes it up
for its flush (behind ingest calls and earlier flushes on the one host
thread), from the window's record."""

import numpy as np


def read(ctx):
    q = ctx.window.get("queue_s")
    return float(np.median(q) * 1e3) if q is not None and len(q) else None
