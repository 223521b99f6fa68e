"""Stacked ingest dispatch: device time of ``_ingest_stacked_lsketch``
(key walk, or the scan for in-batch slot re-claims; ring reclaim, delta
slices) per launch, from the device trace. Each ingest call's batch is
launched once (by the next ingest call in a closed loop, by the next
flush in an open one, which waits for it); where the traced
window holds launches that differ from its ingest calls by more than one
at each end (events dropped inside it), the metric is left out rather
than averaged over the launches that survived."""


def read(ctx):
    m = ctx.trace["modules"].get("_ingest_stacked_lsketch")
    calls = ctx.trace["host_n"].get("ingest", 0)
    if not m or not m[1] or abs(m[1] - calls) > 2:
        return None
    return m[0] / m[1] * 1e3
