"""Query frontend: the median host time of one ``SketchServer.flush`` in
the window (grouping, the dispatch of a staged ingest batch and the plane
prewarm it needs, pad and slice, the query programs' dispatch and the
answers' read-back), from the benchmark's own spans."""

import numpy as np


def read(ctx):
    t = ctx.spans.get("flush")
    return float(np.median(t) * 1e3) if t else None
