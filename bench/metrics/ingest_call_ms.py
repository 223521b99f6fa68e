"""Host partition and dispatch: the median host time of one
``SketchServer.ingest`` call (``AsyncIngestor.submit`` ->
``_partition_stack``, the dispatch of the staged batch, and the plane
prewarm that waits for it), from the benchmark's own spans."""

import numpy as np


def read(ctx):
    t = ctx.spans.get("ingest")
    return float(np.median(t) * 1e3) if t else None
