"""The one load generator: turns a traffic mix (a data file of parameters)
into a fixed schedule from the seed, warms the shapes that schedule uses,
and drives the served path through it for the measured window.

A mix has two parts.

``ingest``: ``batch`` edges per ``SketchServer.ingest`` call, and either
``"mode": "closed"`` (the next batch is submitted as soon as the call
returns, for the whole window; the stream is generated for
``max_edges_per_s`` so a faster program does not run dry) or
``"mode": "rate"`` with ``edges_per_s`` (batch ``j`` is due at
``j * batch / edges_per_s`` seconds into the window).

``queries`` (optional): ``per_s`` point queries, due at fixed times drawn
from the seed (uniform order statistics over the window, so every seed
offers the same number), with kinds in the exact ``mix`` shares. Each
query takes its endpoints from one of the ``recent_edges`` edges due last
before it. The loop submits every due ingest batch, then up to
``max_per_flush`` due queries, then flushes; a query's latency runs from
its due time until its answer is on the host, of which the part until
its flush is taken up (behind ingest calls and earlier flushes) is its
queue time.
"""

from __future__ import annotations

import contextlib
import math
import sys
import time
import traceback

import numpy as np

CLOCK = time.perf_counter


class Spans:
    """Host spans of the benchmark's own calls into the program, kept in
    memory; with ``annotate`` each is also a profiler trace annotation
    ``bench:<name>`` on the trace's clock."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.times: dict = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation("bench:" + name)
        with ann:
            t0 = CLOCK()
            try:
                yield
            finally:
                self.times.setdefault(name, []).append(CLOCK() - t0)


def merge(base: dict, over: dict) -> dict:
    """Deep merge of a workload's parameters over its traffic mix."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


class Plan:
    """The fixed schedule of one run: ingest batches (host columns), their
    due times (None: closed loop), and the query requests with due times."""

    def __init__(self, mix: dict, stream, warm_edges: int, seconds: float,
                 seed: int):
        ing = mix["ingest"]
        self.batch = int(ing["batch"])
        self.closed = ing["mode"] == "closed"
        self.seconds = float(seconds)
        self.warm_edges = warm_edges
        if self.closed:
            n_batches = math.ceil(float(ing["max_edges_per_s"]) * seconds
                                  / self.batch)
            self.ingest_due = None
        else:
            gap = self.batch / float(ing["edges_per_s"])
            n_batches = math.ceil(self.seconds / gap)
            self.ingest_due = np.arange(n_batches) * gap
        self.n_edges = warm_edges + n_batches * self.batch
        stream.extend(self.n_edges)
        self.stream = stream
        self.batches = [stream.slice(a, a + self.batch)
                        for a in range(warm_edges, self.n_edges, self.batch)]
        self.warm_batches = [stream.slice(a, a + self.batch)
                             for a in range(0, warm_edges, self.batch)]
        self.queries = self._queries(mix.get("queries"), seed)

    def _queries(self, q: dict | None, seed: int):
        if not q:
            return None
        rng = np.random.default_rng([int(seed) % (1 << 64), 2])
        n = int(round(float(q["per_s"]) * self.seconds))
        due = np.sort(rng.uniform(0.0, self.seconds, n))
        shares = np.array([g["share"] for g in q["mix"]], np.float64)
        counts = np.floor(shares / shares.sum() * n).astype(int)
        counts[: n - counts.sum()] += 1  # exact counts, leftovers first
        group = rng.permutation(np.repeat(np.arange(len(counts)), counts))
        # endpoints: one of the newest ``recent_edges`` edges due by then
        if self.closed:
            raise ValueError("queries need an ingest rate (mode 'rate')")
        due_edges = self.warm_edges + self.batch * np.searchsorted(
            self.ingest_due, due, side="right")
        recent = int(q["recent_edges"])
        lo = np.maximum(due_edges - recent, 0)
        edge = lo + (rng.random(n) * (due_edges - lo)).astype(np.int64)
        cols = self.stream.cols
        reqs = []
        for g, e in zip(group, edge):
            reqs.append(request(q["mix"][g], cols, int(e)))
        self.max_per_flush = int(q["max_per_flush"])
        self.groups = q["mix"]
        return {"due": due, "group": group, "edge": edge, "reqs": reqs}


def request(g: dict, cols: dict, e: int) -> tuple:
    """The ``SketchServer.submit`` call of query group ``g`` on stream
    edge ``e``: ``(kind, kwargs)``."""
    kind, le = g["kind"], g.get("le", False)
    args = {}
    if kind == "edge":
        args = {"src": int(cols["src"][e]), "la": int(cols["src_label"][e]),
                "dst": int(cols["dst"][e]), "lb": int(cols["dst_label"][e])}
    elif kind == "vertex":
        out = g["direction"] == "out"
        args = {"v": int(cols["src" if out else "dst"][e]),
                "lv": int(cols["src_label" if out else "dst_label"][e]),
                "direction": g["direction"]}
    elif kind == "label":
        out = g["direction"] == "out"
        args = {"lv": int(cols["src_label" if out else "dst_label"][e]),
                "direction": g["direction"]}
    else:
        raise ValueError(f"unknown query kind {kind!r}")
    if le:
        args["le"] = int(cols["edge_label"][e])
    return kind, args


def edge_batch(cols: dict):
    from repro.core.types import EdgeBatch
    return EdgeBatch(**cols)


def warm(server, plan: Plan, sync) -> None:
    """Set-up: ingest the warm-up edges (every ring slot claimed and at
    least one reclaimed, so the walk and the boundary scan, both delta
    variants, plane builds and folds all run), then, for a query mix, one
    flush per group at every pad bucket the window can produce and one
    flush of each size up to ``max_per_flush`` (the frontend's pad and
    slice steps compile per size)."""
    for b in plan.warm_batches:
        server.ingest(edge_batch(b))
    sync()
    if plan.queries is None:
        return
    cols, cap = plan.stream.cols, plan.max_per_flush
    e = plan.warm_edges - 1
    sizes = []
    n = 32
    while True:
        sizes.append(min(n, cap))
        if n >= cap:
            break
        n *= 2
    for g in plan.groups:
        for n in sizes:
            for _ in range(n):
                kind, args = request(g, cols, e)
                server.submit(kind, **args)
            server.flush()
    g = next((g for g in plan.groups if not g.get("le")), plan.groups[0])
    for n in range(1, cap + 1):
        for _ in range(n):
            kind, args = request(g, cols, e)
            server.submit(kind, **args)
        server.flush()


def closed_window(server, plan: Plan, spans: Spans, sync) -> dict:
    """Back-to-back ingest for ``plan.seconds``; the window ends when the
    state holding the last submitted edge is ready."""
    t0 = CLOCK()
    i = 0
    while i < len(plan.batches):
        with spans("ingest"):
            server.ingest(edge_batch(plan.batches[i]))
        i += 1
        if CLOCK() - t0 >= plan.seconds:
            break
    with spans("wait"):
        sync()
    t1 = CLOCK()
    return {"t0": t0, "t1": t1, "batches": i, "edges": i * plan.batch,
            "ran_dry": i == len(plan.batches), "failed": 0}


def open_window(server, plan: Plan, spans: Spans, sync) -> dict:
    """The open loop: the schedule does not wait for the server."""
    q = plan.queries
    due_b = plan.ingest_due
    nb = len(plan.batches)
    due_q = q["due"] if q else np.zeros(0)
    nq = len(due_q)
    answers = np.full(nq, -1, np.int64)
    done_at = np.full(nq, np.nan)
    start_at = np.full(nq, np.nan)
    n_at = np.zeros(nq, np.int64)
    failed = np.zeros(nq, bool)
    late_b = np.zeros(nb)
    edges = plan.warm_edges
    t0 = CLOCK()
    bi = qi = 0
    while bi < nb or qi < nq:
        now = CLOCK() - t0
        while bi < nb and due_b[bi] <= now:
            with spans("ingest"):
                server.ingest(edge_batch(plan.batches[bi]))
            late_b[bi] = CLOCK() - t0 - due_b[bi]
            edges += plan.batch
            bi += 1
            now = CLOCK() - t0
        hi = qi
        while hi < nq and due_q[hi] <= now and hi - qi < plan.max_per_flush:
            hi += 1
        if hi > qi:
            start_at[qi:hi] = now
            with spans("submit"):
                reqs = [server.submit(q["reqs"][j][0], **q["reqs"][j][1])
                        for j in range(qi, hi)]
            try:
                with spans("flush"):
                    server.flush()
                answers[qi:hi] = [r.answer for r in reqs]
            except Exception:  # a failed flush fails its queries
                traceback.print_exc(file=sys.stderr)
                server.pending.clear()
                failed[qi:hi] = True
            done_at[qi:hi] = CLOCK() - t0
            n_at[qi:hi] = edges
            qi = hi
            continue
        if bi == nb and qi == nq:  # the schedule ended on an ingest batch
            break
        nxt = min(due_b[bi] if bi < nb else math.inf,
                  due_q[qi] if qi < nq else math.inf)
        wait = nxt - (CLOCK() - t0)
        if wait > 0:
            with spans("sleep"):
                time.sleep(wait)
    with spans("wait"):
        sync()
    t1 = CLOCK()
    lat = done_at - due_q
    return {"t0": t0, "t1": t1, "batches": nb, "edges": nb * plan.batch,
            "answers": answers, "n_at": n_at, "latency_s": lat,
            "queue_s": start_at - due_q,
            "failed": int(failed.sum()), "failed_mask": failed,
            "ingest_late_s": late_b, "ran_dry": False}


def run_window(server, plan: Plan, spans: Spans, sync) -> dict:
    if plan.closed:
        return closed_window(server, plan, spans, sync)
    return open_window(server, plan, spans, sync)
