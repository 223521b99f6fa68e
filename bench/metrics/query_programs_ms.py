"""Query programs: device time of the plane-walk query programs
(``_edge_pallas``, ``_vertex_pallas``, ``_label_pallas``) per flush in the
traced window, from the device trace."""

PROGRAMS = ("_edge_pallas", "_vertex_pallas", "_label_pallas")


def read(ctx):
    t = sum(ctx.trace["modules"].get(p, [0.0, 0])[0] for p in PROGRAMS)
    n = ctx.trace["host_n"].get("flush", 0)
    return t / n * 1e3 if n and t else None
