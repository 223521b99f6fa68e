"""Load loop: the median time an open loop's ingest batch is submitted
after its due time (behind a flush on the one host thread), from the
window's record."""

import numpy as np


def read(ctx):
    late = ctx.window.get("ingest_late_s")
    return float(np.median(late) * 1e3) if late is not None and len(
        late) else None
