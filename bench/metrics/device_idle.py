"""Device idle share of the traced window (``device_idle.<cell kind>``:
the ingest window, or the serving window of ingest and queries): one
minus the union of the intervals in which a program ran on the device,
over the window."""


def read(ctx):
    w = ctx.trace["window_s"]
    return 100.0 * (1.0 - ctx.trace["busy_s"] / w) if w > 0 else None
