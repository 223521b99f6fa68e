"""Plane cache under ingest: device time of ``_build_planes`` and
``_apply_planes_delta`` (the serving prewarm after every batch: inside
the next ingest call in a closed loop, inside the next flush in an open
one) per ingest call in the traced window, from the device trace."""

PROGRAMS = ("_build_planes", "_apply_planes_delta")


def read(ctx):
    t = sum(ctx.trace["modules"].get(p, [0.0, 0])[0] for p in PROGRAMS)
    n = ctx.trace["host_n"].get("ingest", 0)
    return t / n * 1e3 if n and t else None
