"""Per-layer readings from what the program records of itself: its host
spans and counters (``repro.sketch.trace``) and the named scopes of the
stacked ingest program (``ingest.*``, ``repro.engine.insert``).

``trace_reduce`` reduces the device planes and the benchmark's own
``bench:`` spans; this module reads, from the same ``.xplane.pb`` and the
program's in-memory record, what lies below them:

* ``load``: the program's spans as the trace holds them (host events
  ``lsketch:<name>``, on the device's clock) and the ``ingest.*`` scope of
  each program's instructions (``hlo_scopes.op_scopes``);
* ``split``: over the window ``trace_reduce.reduce`` read,

  - ``scopes``: device time per program and scope; an operation counts
    under its instruction's scope, ``unscoped`` where it has none, and
    belongs to the program launch whose interval holds its start;
  - ``ingest_launches``: each ``_ingest_stacked_lsketch`` launch that lies
    wholly inside the window, in time order: its program time (as
    ``trace_reduce`` counts it), its operations' time by scope, and
    whether it ran operations of the boundary scan (``ingest.scan``).
    Where the device stopped recording (``cut``), the last program it
    recorded is left out: its record stops where the device's did (one
    v5e recorded a 331 ms scan launch as 134 ms);
  - ``unscoped_ops``: that program's operations under no scope, longest
    first;
  - ``idle_spans``: each idle gap of ``trace_reduce``'s ``idle_gaps`` put
    on the innermost host span open at its middle, named ``<bench
    span>/<program span>`` (``ingest/planes.wait``), or ``<bench span>``
    where no program span was open: the entries under one benchmark span
    sum to its ``idle_gaps`` entry;
* ``readings``: the per-layer numbers these give (``READINGS``).

The harness does not read these yet: enabling the program's record for
the measured window and passing ``load``'s and ``split``'s results to the
metric readers needs an edit of ``harness.py`` (and of
``trace_reduce.breakdown`` for ``idle_spans``), which PERF.md §7 lists.
"""

from __future__ import annotations

import numpy as np

from . import hlo_scopes
from . import trace_reduce as tr

PREFIX = "lsketch:"
INGEST = "_ingest_stacked_lsketch"


def load(path: str) -> dict:
    """``{"spans": [(name, start_s, dur_s), ...], "op_scopes": {...}}``:
    the program's spans in the trace and the scope of each program's
    instructions."""
    from jax.profiler import ProfileData
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(n[len(PREFIX):], t, d) for n, t, d in
                          tr._events(line) if n.startswith(PREFIX)]
    return {"spans": spans, "op_scopes": hlo_scopes.op_scopes(path)}


def innermost(spans: list, at) -> list:
    """For each time in ``at``, the name of the innermost of ``spans``
    (``(name, start_s, dur_s)``) open then, the one that began last, or
    None."""
    if not spans or not len(at):
        return [None] * len(at)
    start = np.array([t for _, t, _ in spans])
    end = start + np.array([d for _, _, d in spans])
    names = [n for n, _, _ in spans]
    out = []
    for i in range(0, len(at), 1024):
        x = np.asarray(at[i:i + 1024])[:, None]
        began = np.where((start <= x) & (end >= x), start, -np.inf)
        j = np.argmax(began, axis=1)
        out += [names[c] if np.isfinite(began[r, c]) else None
                for r, c in enumerate(j)]
    return out


def split(raw: dict, red: dict, prog: dict) -> dict:
    """The module docstring's ``split``: ``raw`` and ``red`` are
    ``trace_reduce.load`` and ``reduce`` of the trace, ``prog`` is
    ``load`` of it."""
    win = [t for n, t, _ in raw["host"] if n == "window"]
    ts = [t for dv in raw["devices"].values() for _, t, _ in dv["ops"]]
    lo = win[0] if win else min(ts, default=0.0)
    hi = lo + red["window_s"]
    bench = [s for s in raw["host"] if s[0] != "window"]
    n_dev = max(len(raw["devices"]), 1)
    scopes, launches, unscoped, idle = {}, [], {}, {}
    for dv in raw["devices"].values():
        progs, iv = [], []  # as trace_reduce.reduce reads the launches
        done = lo
        for name, t, d in sorted(dv["modules"], key=lambda e: e[1]):
            if t + d < lo or t > hi:
                continue
            a, b = max(t, done), min(t + d, hi)
            done = max(done, b)
            progs.append((t, t + d, name, max(b - a, 0.0)))
            iv.append((max(t, lo), min(t + d, hi)))
        if not dv["modules"]:
            iv = [(max(t, lo), min(t + d, hi)) for n, t, d in dv["ops"]
                  if t + d >= lo and t <= hi and not tr.CONTAINER.match(n)]
        launches += by_scope(progs, dv["ops"], prog["op_scopes"], lo, hi,
                             red["cut"], scopes, unscoped)
        g = tr.gaps(np.array(iv, np.float64).reshape(-1, 2), lo, hi)
        mid = g.mean(axis=1)
        for b, p, (s, e) in zip(innermost(bench, mid),
                                innermost(prog["spans"], mid), g.tolist()):
            key = f"{b or 'other'}/{p}" if p else b or "other"
            idle[key] = idle.get(key, 0.0) + (e - s) / n_dev
    order = lambda d: sorted(d.items(), key=lambda kv: -kv[1])
    return {"scopes": scopes,
            "ingest_launches": sorted(launches, key=lambda r: r["start"]),
            "unscoped_ops": order(unscoped), "idle_spans": order(idle)}


def by_scope(progs: list, ops: list, op_scopes: dict, lo: float,
             hi: float, cut: bool, scopes: dict, unscoped: dict) -> list:
    """Add one device's operation time inside ``[lo, hi]`` to ``scopes``
    (program -> scope -> s) and, for the ingest program's unscoped
    operations, to ``unscoped``; return its ingest launches that lie
    wholly inside (with ``cut``, the last program recorded is not
    whole). ``progs`` holds each launch's ``(start, end, name, program
    time)`` in time order."""
    starts = np.array([p[0] for p in progs])
    whole = len(progs) - 1 if cut else len(progs)
    launch = {i: {"start": t, "program_s": ps, "scopes": {}, "scan": False}
              for i, (t, e, name, ps) in enumerate(progs[:whole])
              if tr.module_name(name) == INGEST and t >= lo and e <= hi}
    at = np.searchsorted(starts, [t for _, t, _ in ops], side="right") - 1
    memo = {}
    for (name, t, d), i in zip(ops, at.tolist()):
        if t + d < lo or t > hi:
            continue
        if i >= 0 and t > progs[i][1]:
            i = -1  # between launches
        module = progs[i][2] if i >= 0 else None
        if (module, name) not in memo:
            memo[module, name] = None if tr.CONTAINER.match(name) else (
                tr.module_name(module) if module else "other",
                op_scopes.get(module, {}).get(name, "unscoped"))
        where = memo[module, name]
        if where is None:
            continue
        program, scope = where
        dd = min(t + d, hi) - max(t, lo)
        per = scopes.setdefault(program, {})
        per[scope] = per.get(scope, 0.0) + dd
        if program == INGEST and scope == "unscoped":
            unscoped[name] = unscoped.get(name, 0.0) + dd
        rec = launch.get(i)
        if rec is not None:
            rec["scopes"][scope] = rec["scopes"].get(scope, 0.0) + dd
            rec["scan"] |= scope == "ingest.scan"
    return list(launch.values())


def per_call(snapshot: dict | None, root: str, name: str,
             self_time: bool = False) -> list | None:
    """From the program's in-memory record (``repro.sketch.trace``
    ``snapshot()``): for each ``root`` span, the summed duration of the
    ``name`` spans inside it, or with ``self_time`` their self time (a
    span's duration less its child spans'). None where no ``name`` span
    was recorded."""
    spans = (snapshot or {}).get("spans") or []
    if not any(s[0] == name and s[2] is not None for s in spans):
        return None
    child = [0.0] * len(spans)
    for _, t0, t1, parent in spans:
        if parent >= 0 and t1 is not None:
            child[parent] += t1 - t0
    root_of, sums = [], {}
    for i, (n, _, _, parent) in enumerate(spans):
        root_of.append(i if n == root else
                       root_of[parent] if parent >= 0 else -1)
        if n == root:
            sums[i] = 0.0
    for i, (n, t0, t1, _) in enumerate(spans):
        if n == name and t1 is not None and root_of[i] in sums:
            sums[root_of[i]] += t1 - t0 - (child[i] if self_time else 0.0)
    return list(sums.values())


def scoped_launches(sp: dict | None) -> list | None:
    """``split``'s ingest launches, or None where no operation of any of
    them carries an ``ingest.*`` scope (a program without the scopes, or a
    trace without its HLO): a missing scope then reads as nothing, not as
    zero."""
    launches = (sp or {}).get("ingest_launches") or []
    if not any(set(r["scopes"]) - {"unscoped"} for r in launches):
        return None
    return launches


def _median_ms(t):
    return float(np.median(t) * 1e3) if t else None


def _mean_ms(t):
    return float(np.mean(t) * 1e3) if t else None


def _share_pct(snap, part, whole):
    counts = (snap or {}).get("counts") or {}
    return 100.0 * counts.get(part, 0) / counts[whole] \
        if counts.get(whole) else None


def _per_launch_ms(sp, scope):
    launches = scoped_launches(sp)
    return _mean_ms([r["scopes"].get(scope, 0.0) for r in launches]) \
        if launches else None


READINGS = {
    # median self time of the numpy hash partition per ingest call
    "partition_ms": lambda snap, sp: _median_ms(per_call(
        snap, "server.ingest", "ingest.partition", self_time=True)),
    # median time per ingest call blocked on the previous batch's delta
    # flag inside the plane prewarm: the call waiting for the device
    "prewarm_wait_ms": lambda snap, sp: _median_ms(per_call(
        snap, "server.ingest", "planes.wait")),
    # mean program time of launches that ran the key walk / the scan
    "walk_launch_ms": lambda snap, sp: _mean_ms(
        [r["program_s"] for r in scoped_launches(sp) or () if not r["scan"]]),
    "scan_launch_ms": lambda snap, sp: _mean_ms(
        [r["program_s"] for r in scoped_launches(sp) or () if r["scan"]]),
    # device time per ingest launch of the ring reclaim, and of the
    # operations no scope names (copies XLA inserts, for one)
    "ring_reclaim_ms": lambda snap, sp: _per_launch_ms(sp, "ingest.reclaim"),
    "ingest_unscoped_ms": lambda snap, sp: _per_launch_ms(sp, "unscoped"),
    # share of the batches partitioned whose shard rows span subwindows:
    # the launches that take the boundary scan, not the key walk
    "scan_batch_share": lambda snap, sp: _share_pct(
        snap, "ingest.boundary_batches", "ingest.batches"),
}


def readings(snapshot: dict | None, sp: dict | None) -> dict:
    """Each of ``READINGS`` from the program's in-memory record and
    ``split``'s result; None where what it reads is absent."""
    return {k: f(snapshot, sp) for k, f in READINGS.items()}
