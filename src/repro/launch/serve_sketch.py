"""Sketch serving driver: batched ingest + batched queries over one handle.

The sketch analog of the decode server in ``serve.py``, rebuilt on the
functional ``repro.sketch`` handle layer (DESIGN.md §6/§7): the server
owns a ``(SketchSpec, AsyncIngestor)`` pair; ingest hash-partitions each
edge batch across ``--shards`` shards in one stacked dispatch (shard-axis
Pallas kernel on TPU, fused scan elsewhere) and is **pipelined** — the
host partition of batch N+1 overlaps batch N's in-flight dispatch
(``--no-pipeline`` dispatches eagerly instead). Queries fan through every
shard and sum contributions; the query path flushes the ingest pipeline
first, so answers always reflect every batch submitted before them.
``--query-path`` picks the read path (DESIGN.md §8/§9): the dense vmapped
scan reference, the shard-axis kernel path over cached window-reduced
planes, or — with ``--mesh N`` laying the shard axis over N devices —
the mesh-resident ``collective`` path (``--collective`` is shorthand),
where queries run inside ``shard_map`` against a device-resident plane
cache and reduce with psum, never funnelling shard partials through the
host. The plane cache is built on the first query after a flush and
reused for every request group until the next ingest. The same server
fronts LSketch, LGS, or GSS because the handle layer dispatches on
``spec.kind``.

With ``--tenants T`` the server fronts a ``skt.TenantPool`` instead of a
single handle (DESIGN.md §11): T independent same-spec tenant sketches
share one stacked state, cross-tenant ingest rounds and query groups each
collapse into a single pooled dispatch (the tenant is a dynamic per-row
axis, not a compile-time one), and every answer is bit-identical to the
tenant's standalone sketch.

Usage: python -m repro.launch.serve_sketch --sketch lsketch --shards 4
       python -m repro.launch.serve_sketch --shards 8 --mesh 4 --collective
       python -m repro.launch.serve_sketch --shards 1 --tenants 16
   (or python -m repro.launch.serve --mode sketch ...)
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Dict, List

import jax
import numpy as np

from repro import sketch as skt
from repro.sketch import trace
from repro.core import LGSConfig, LSketchConfig
from repro.core.gss import gss_config
from repro.data.stream import PHONE, edge_batches, generate
from repro.launch.compile_cache import enable_compile_cache


@dataclasses.dataclass
class QueryRequest:
    """One pending query; ``answer`` is filled by ``SketchServer.flush``."""

    kind: str  # "edge" | "vertex" | "label"
    args: Dict[str, Any]
    answer: int | None = None
    tenant: Any = None  # pool-mode routing (None on a single-sketch server)


class SketchServer:
    """Continuous-batching frontend over one sharded sketch handle — or,
    with ``pool=``, over a ``skt.TenantPool`` of many same-spec sketches
    (DESIGN.md §11).

    ``submit`` enqueues; ``flush`` answers every pending request with one
    batched dispatch per (kind, edge-label?, last?, direction?) group —
    the static axes of the underlying jitted queries. In pool mode the
    tenant is a *dynamic* axis (a per-row slot vector), so one group still
    answers in one pooled dispatch regardless of how many tenants it spans.

    Ingest rides a ``skt.AsyncIngestor`` (``pipeline=True``, the default):
    the host hash-partition of each batch overlaps the previous batch's
    device dispatch, and the query path flushes the pipeline before
    answering — submitted batches are always visible to later queries. In
    pool mode the pool's own pipelined rounds play that role
    (``ingest(batch, tenant=...)`` per tenant, or ``ingest_many`` for one
    cross-tenant round), under the deterministic cross-tenant flush
    contract of ``skt.TenantPool.submit``: per-tenant submission order is
    preserved, cross-tenant order is normalized by slot — the resulting
    state is bit-identical for any caller iteration order (DESIGN.md
    §7.3/§11, pinned in tests/test_tenant_pool.py).
    """

    def __init__(self, spec: "skt.SketchSpec | None" = None,
                 max_batch: int = 4096,
                 state: "skt.ShardedState | None" = None,
                 pipeline: bool = True, query_path: str = "auto",
                 mesh=None, axis: str = "data", prewarm: bool = True,
                 pool: "skt.TenantPool | None" = None,
                 heat_threshold: float | None = None,
                 split_replicas: int | None = None,
                 horizons=None):
        # registered time-sensitive sweep: prewarm builds ALL of these
        # horizons in one fused multi-horizon dispatch (DESIGN.md §14) and
        # per-horizon query groups slice out of the stacked entry
        self.horizons = [h if h is None else int(h)
                         for h in (horizons or [])]
        if any(h is not None and h <= 0 for h in self.horizons):
            raise ValueError("horizons= entries must be positive (or None "
                             "for the full window)")
        self.pool = pool
        if pool is not None:
            if spec is not None and spec != pool.spec:
                raise ValueError("pass either spec= or pool=, and a pool "
                                 "carries its own per-tenant spec")
            if state is not None or mesh is not None:
                raise ValueError(
                    "pool mode owns its state and is host-resident: "
                    "state=/mesh= do not apply (DESIGN.md §11)")
            if query_path == "collective":
                raise ValueError(
                    "query_path='collective' serves one mesh-placed "
                    "sketch, not a TenantPool")
            if heat_threshold is not None:
                raise ValueError("heat_threshold= tracks a single handle's "
                                 "stream; pool tenants route per-spec")
            self.spec = pool.spec
            self.pipeline = pipeline
            self.query_path = query_path
            self.prewarm = prewarm
            self._ingestor = None
            self.max_batch = max_batch
            self.pending: List[QueryRequest] = []
            self.query_shard_counts = np.zeros(pool.spec.n_shards, np.int64)
            return
        if spec is None:
            raise ValueError("SketchServer needs a spec= or a pool=")
        self.spec = spec
        self.pipeline = pipeline
        self.query_path = query_path
        self.prewarm = prewarm
        # a pre-placed handle already carries its layout — honor it
        ctx = skt.mesh_context(state) if state is not None else None
        if ctx is None and mesh is not None:
            ctx = skt.MeshContext(mesh=mesh, axis=axis)
        if query_path == "collective":
            # fail at construction, not after a full ingest: collective
            # needs a mesh whose axis divides the shard count
            if ctx is None:
                raise ValueError(
                    "query_path='collective' needs a mesh (SketchServer("
                    "..., mesh=...) or a place()d state)")
            if not ctx.divides(spec.n_shards):
                raise ValueError(
                    f"query_path='collective' needs the mesh axis to divide "
                    f"the shard count: n_shards={spec.n_shards} over "
                    f"{ctx.n_devices} devices replicates instead of "
                    "sharding")
        if mesh is not None and skt.mesh_context(state) is None:
            # mesh-resident serving: the shard axis lives on the mesh from
            # the first dispatch; ingest keeps the residency (DESIGN.md §9)
            state = skt.place(spec, state if state is not None
                              else skt.create(spec), mesh, axis=axis)
        self._ingestor = skt.AsyncIngestor(spec, state=state,
                                           heat_threshold=heat_threshold,
                                           split_replicas=split_replicas)
        self.max_batch = max_batch
        self.pending: List[QueryRequest] = []
        # per-shard query-endpoint log (DESIGN.md §13): every answered
        # edge/vertex request increments its endpoint's *home* shard — the
        # gSketch workload signal ``budget_report`` blends with ingest load
        self.query_shard_counts = np.zeros(spec.n_shards, np.int64)

    @property
    def state(self) -> "skt.ShardedState":
        """The handle with every ingested batch applied (flushes)."""
        if self.pool is not None:
            return self.pool.state
        return self._ingestor.state

    @property
    def live_spec(self) -> "skt.SketchSpec":
        """The spec carrying the *live* routing table — the constructor's
        spec plus any splits the heavy-key detector applied since
        (DESIGN.md §13). Same identity as ``self.spec`` (routing is
        compare-excluded); checkpoint with this one so the manifest
        records the table."""
        if self.pool is not None:
            return self.pool.spec
        return self._ingestor.spec

    def budget_report(self, alpha: float = 0.5) -> "skt.BudgetReport":
        """Workload-aware sizing report (``skt.recommend_budget``): the
        ingest-side heavy-key summary blended with this server's
        query-endpoint log into per-shard load fractions plus the routing
        table that levels them. Apply to stored history with
        ``skt.reshard(spec, state, n_shards, routing=report.routing)``
        and to future ingest by serving with
        ``spec.replace(routing=report.routing)``."""
        if self.pool is not None:
            raise ValueError("budget_report() sizes a single handle; pool "
                             "tenants carry per-spec routing")
        det = self._ingestor.detector
        if det is None:
            raise ValueError("budget_report() needs the heavy-key detector "
                             "(construct with heat_threshold=...)")
        return skt.recommend_budget(self.live_spec, det,
                                    self.query_shard_counts, alpha=alpha)

    def _log_query_endpoints(self, kind: str, q: "skt.QueryBatch") -> None:
        if kind == "edge":
            v, lv = q.src, q.src_label
        elif kind == "vertex":
            v, lv = q.vertex, q.vertex_label
        else:  # label aggregates touch every shard equally: no signal
            return
        self.query_shard_counts += np.bincount(
            skt.shard_assignment(self.spec, np.asarray(v), np.asarray(lv)),
            minlength=self.spec.n_shards).astype(np.int64)

    # ---- ingest ----
    def ingest(self, batch, tenant=None) -> None:
        """Submit one batch (a ``server.ingest`` span while
        ``repro.sketch.trace`` is on: the dispatch of the batch staged
        before it, this batch's partition, and the plane prewarm)."""
        if self.pool is not None:
            if tenant is None:
                raise ValueError("pool-mode ingest needs tenant=")
            self.ingest_many([(tenant, batch)])
            return
        if tenant is not None:
            raise ValueError("tenant= needs a pool-mode server (pool=)")
        with trace.span("server.ingest"):
            self._ingestor.submit(batch)
            if not self.pipeline:
                self._ingestor.flush()
            self._prewarm_many([None])

    def ingest_many(self, batches) -> None:
        """One cross-tenant ingest round (pool mode): ``{tenant: batch}``
        or ``(tenant, batch)`` pairs collapse into a single pooled
        dispatch. Deterministic under any iteration order — the pool
        normalizes cross-tenant layout by slot and preserves per-tenant
        pair order (the §7.3 flush contract, extended in §11)."""
        if self.pool is None:
            raise ValueError("ingest_many needs a pool-mode server (pool=)")
        self.pool.submit(batches)
        if not self.pipeline:
            self.pool.flush()
        self._prewarm_many([None])

    def _prewarm(self, last=None, handle=None) -> None:
        """Keep the plane cache hot off the query path (DESIGN.md §10).

        Runs on the *dispatched* handle — the staged pipeline batch stays
        staged, so prewarming never collapses the partition/dispatch
        overlap. Each call folds the flush's delta chain (or, after a
        window advance, pays the rebuild here instead of inside the first
        query). No-op for the scan path: it reads raw counters.
        """
        if not self.prewarm:
            return
        path = skt.resolve_query_path(self.spec, self.query_path)
        if path == "scan":
            return
        if self.pool is not None:
            skt.query_planes(self.spec,
                             handle if handle is not None
                             else self.pool.dispatched,
                             last, groups=self.pool.n_slots)
            return
        h = handle if handle is not None else self._ingestor.dispatched
        if h is None:
            return
        skt.query_planes(self.spec, h, last,
                         collective=(path == "collective"))

    def _prewarm_many(self, lasts, handle=None) -> None:
        """Fused multi-horizon prewarm (DESIGN.md §14): when one flush (or
        the registered ``horizons=`` sweep) needs planes at several
        horizons, ONE stacked build covers them all — O(k + H) ring work
        instead of O(H·k) — and per-horizon lookups slice out of the
        cached ``MultiPlanes`` entry. A single wanted horizon with no
        registered sweep falls back to the plain per-horizon prewarm."""
        if not self.prewarm:
            return
        with trace.span("planes.prewarm"):
            path = skt.resolve_query_path(self.spec, self.query_path)
            if path == "scan":
                return
            want = list(dict.fromkeys(lasts))
            for h in self.horizons:
                if h not in want:
                    want.append(h)
            if not want:
                return
            if len(want) == 1:
                self._prewarm(want[0], handle=handle)
                return
            if self.pool is not None:
                h0 = handle if handle is not None else self.pool.dispatched
                skt.query_planes_multi(self.spec, h0, want,
                                       groups=self.pool.n_slots)
                return
            h0 = handle if handle is not None else self._ingestor.dispatched
            if h0 is None:
                return
            skt.query_planes_multi(self.spec, h0, want,
                                   collective=(path == "collective"))

    def serving_summary(self, alpha: float = 0.5) -> str:
        """One-line serving-health summary for periodic operator logging:
        queue depth, plane-cache temperature, and — when the heavy-key
        detector is on — the workload-aware sizing numbers from
        ``budget_report()`` so skew shows up in the log before anyone
        decides to reshard (DESIGN.md §13)."""
        from repro.sketch.query import PLANES_BUILD_COUNTS as c
        parts = [f"pending={len(self.pending)}",
                 f"planes[build={c['build']} delta={c['delta']} "
                 f"evict={c['evict']}]"]
        if self.pool is None and self._ingestor.detector is not None:
            rep = self.budget_report(alpha)
            live = self.live_spec.routing.splits \
                if self.live_spec.routing else ()
            parts.append(
                f"splits[live={len(live)} recommended="
                f"{len(rep.routing.splits)}] "
                f"load=[{' '.join('%.3f' % f for f in rep.combined)}]")
        return " ".join(parts)

    # ---- queries ----
    def submit(self, kind: str, tenant=None, **args) -> QueryRequest:
        if (tenant is None) != (self.pool is None):
            raise ValueError("tenant= is required in pool mode and invalid "
                             "otherwise")
        req = QueryRequest(kind, args, tenant=tenant)
        self.pending.append(req)
        if len(self.pending) >= self.max_batch:
            self.flush()
        return req

    def _group_key(self, r: QueryRequest):
        # the tenant is deliberately absent: in pool mode it is a dynamic
        # per-row axis of the pooled dispatch, not a compile-time group
        return (r.kind, r.args.get("le") is not None, r.args.get("last"),
                r.args.get("direction", "out"))

    @staticmethod
    def _group_batch(kind, reqs, with_le, last, direction) -> "skt.QueryBatch":
        a = {k: np.asarray([r.args[k] for r in reqs], np.int32)
             for k in reqs[0].args if _batch_axis(reqs, k)}
        le = a.get("le") if with_le else None
        if kind == "edge":
            return skt.QueryBatch.edges(a["src"], a["la"], a["dst"],
                                        a["lb"], edge_label=le, last=last)
        if kind == "vertex":
            return skt.QueryBatch.vertices(a["v"], a["lv"], edge_label=le,
                                           direction=direction, last=last)
        if kind == "label":
            return skt.QueryBatch.labels(a["lv"], edge_label=le,
                                         direction=direction, last=last)
        raise ValueError(f"unknown query kind {kind!r}")

    def flush(self) -> int:
        if not self.pending:  # nothing queued: no dispatch, no state touch
            return 0
        done = 0
        groups: Dict[tuple, List[QueryRequest]] = {}
        for r in self.pending:
            groups.setdefault(self._group_key(r), []).append(r)
        # post-flush handle: .state drains the ingest pipeline first; a
        # flush spanning several horizons prewarms them in ONE fused build
        self._prewarm_many([g[2] for g in groups], handle=self.state)
        for (kind, with_le, last, direction), reqs in groups.items():
            if self.pool is not None:
                # one pooled dispatch for the whole group: contiguous
                # per-tenant runs keep the combine cheap, and stable
                # sorting keeps the row layout deterministic under any
                # arrival interleaving
                order = sorted(range(len(reqs)),
                               key=lambda i: self.pool.slot_of(
                                   reqs[i].tenant)
                               if reqs[i].tenant in self.pool.tenants
                               else -1)
                runs: List[tuple] = []  # (tenant, [req, ...]) runs
                for i in order:
                    r = reqs[i]
                    if runs and runs[-1][0] == r.tenant:
                        runs[-1][1].append(r)
                    else:
                        runs.append((r.tenant, [r]))
                pairs = [(t, self._group_batch(kind, rs, with_le, last,
                                               direction))
                         for t, rs in runs]
                outs = self.pool.query_many(pairs, path=self.query_path)
                for (_, rs), out in zip(runs, outs):
                    for r, v in zip(rs, np.asarray(out)):
                        r.answer = int(v)
                done += len(reqs)
                continue
            q = self._group_batch(kind, reqs, with_le, last, direction)
            self._log_query_endpoints(kind, q)
            out = np.asarray(skt.query(self.spec, self.state, q,
                                       path=self.query_path))
            for r, v in zip(reqs, out):
                r.answer = int(v)
            done += len(reqs)
        self.pending.clear()
        return done

    # ---- analytics (DESIGN.md §12) ----
    def top_k(self, kind: str = "vertex", k: int = 10, *,
              direction: str = "out", last=None, horizons=None, tenant=None):
        """Windowed heavy-hitter top-k over the served sketch — ``kind``
        "vertex" -> (vids, weights), "edge" -> (src, dst, weights),
        "label" -> (blocks, weights), each a ``[k]`` tuple padded with
        (-1, 0). Pool mode answers for one tenant (``tenant=``). Flushes
        pending queries first so the ranking reflects every prior submit;
        the dispatch reuses the same plane cache the query path keeps hot.
        ``horizons=[h1, ..., hH]`` (exclusive with ``last=``) sweeps the
        ranking across time horizons in one fused dispatch — result
        leaves gain a leading ``[H]`` axis (DESIGN.md §14).
        """
        self.flush()
        if self.pool is not None:
            if tenant is None:
                raise ValueError("pool-mode top_k needs tenant=")
            return self.pool.top_k(tenant, kind=kind, k=k,
                                   direction=direction, last=last,
                                   horizons=horizons)
        if tenant is not None:
            raise ValueError("tenant= needs a pool-mode server (pool=)")
        st = self.state
        if kind == "vertex":
            return skt.heavy_vertices(self.spec, st, k, direction=direction,
                                      last=last, horizons=horizons,
                                      path=self.query_path)
        if kind == "edge":
            return skt.heavy_edges(self.spec, st, k, last=last,
                                   horizons=horizons, path=self.query_path)
        if kind == "label":
            return skt.top_labels(self.spec, st, k, direction=direction,
                                  last=last, horizons=horizons,
                                  path=self.query_path)
        raise ValueError(f"unknown top_k kind {kind!r}")

    def reachable(self, src, src_label, dst, dst_label, *,
                  max_hops: int = 8, last=None, horizons=None, tenant=None):
        """Batched multi-hop reachability (bool [B]) over the served
        sketch; pool mode extracts the tenant's standalone handle.
        ``last=`` restricts edges to recent windows; ``horizons=`` sweeps
        that restriction and returns bool ``[H, B]`` (DESIGN.md §14)."""
        self.flush()
        if self.pool is not None:
            if tenant is None:
                raise ValueError("pool-mode reachable needs tenant=")
            spec, st = self.pool.handle_of(tenant)
            return skt.reachable_many(spec, st, src, src_label, dst,
                                      dst_label, max_hops=max_hops,
                                      last=last, horizons=horizons)
        if tenant is not None:
            raise ValueError("tenant= needs a pool-mode server (pool=)")
        return skt.reachable_many(self.spec, self.state, src, src_label,
                                  dst, dst_label, max_hops=max_hops,
                                  last=last, horizons=horizons)


def _batch_axis(reqs: List[QueryRequest], k: str) -> bool:
    """Request fields that batch into arrays (vs the static grouping axes)."""
    return k not in ("direction", "last") and \
        all(r.args.get(k) is not None for r in reqs)


def build_spec(name: str, window_size: int, n_shards: int = 1) -> "skt.SketchSpec":
    if name == "lgs":
        cfg = LGSConfig(d=128, copies=3, c=8, k=8, window_size=window_size)
    elif name == "gss":
        cfg = gss_config(d=128)
    else:
        cfg = LSketchConfig(d=128, n_blocks=2, F=1024, r=8, s=8, c=16, k=8,
                            window_size=window_size, pool_capacity=4096,
                            pool_probes=16)
    return skt.SketchSpec(kind=name, config=cfg, n_shards=n_shards)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--sketch", default="lsketch",
                    choices=["lsketch", "lgs", "gss"])
    ap.add_argument("--shards", type=int, default=1,
                    help="hash-partitioned sketch shards (leading state axis)")
    ap.add_argument("--edges", type=int, default=20000)
    ap.add_argument("--requests", type=int, default=4096)
    ap.add_argument("--ingest-batch", type=int, default=2048)
    ap.add_argument("--no-pipeline", action="store_true",
                    help="dispatch each batch eagerly instead of "
                         "overlapping partition and device compute")
    ap.add_argument("--query-path", default="auto",
                    choices=["auto", "scan", "pallas", "collective"],
                    help="read path: dense vmapped scan, shard-axis "
                         "kernels over cached window-reduced planes, or "
                         "the mesh-resident shard_map+psum path "
                         "(needs --mesh)")
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="lay the shard axis over the first N devices "
                         "(0 = host-resident handle); N must divide "
                         "--shards for the collective path")
    ap.add_argument("--collective", action="store_true",
                    help="shorthand for --query-path collective")
    ap.add_argument("--no-prewarm", action="store_true",
                    help="skip keeping the plane cache hot across ingest "
                         "flushes; the first query after a flush pays the "
                         "delta-apply or rebuild inline")
    ap.add_argument("--topk", type=int, default=5,
                    help="heavy-hitter summary size printed after serving "
                         "(reversible-sketch analytics, DESIGN.md §12)")
    ap.add_argument("--heat-threshold", type=float, default=0.0,
                    help="skew-aware routing (DESIGN.md §13): split any "
                         "source key carrying more than this fraction of "
                         "the ingest stream across replica shards (0 = "
                         "off); prints the workload-aware budget report "
                         "after serving")
    ap.add_argument("--horizons", default="", metavar="H1,H2,...",
                    help="register a time-sensitive horizon sweep (window "
                         "counts, e.g. 1,2,4,8): prewarm builds every "
                         "registered horizon in ONE fused multi-horizon "
                         "plane dispatch (DESIGN.md §14) and a sweep "
                         "summary prints after serving")
    ap.add_argument("--summary-every", type=int, default=0, metavar="N",
                    help="print a serving-health summary every N ingest "
                         "batches (queue depth, plane-cache counters, and "
                         "the workload-aware budget report when "
                         "--heat-threshold is on); 0 = off")
    ap.add_argument("--tenants", type=int, default=0, metavar="T",
                    help="serve T independent tenant sketches from one "
                         "TenantPool (stream split round-robin; each "
                         "tenant gets --shards shards). Incompatible "
                         "with --mesh/--collective (pool mode is "
                         "host-resident, DESIGN.md §11)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.tenants and (args.mesh or args.collective):
        raise SystemExit("--tenants is host-resident: drop --mesh/"
                         "--collective")
    if args.tenants and args.heat_threshold:
        raise SystemExit("--heat-threshold tracks a single handle's "
                         "stream: drop --tenants")
    if args.collective:
        args.query_path = "collective"

    mesh = None
    if args.mesh:
        devs = jax.devices()
        if args.mesh > len(devs):
            raise SystemExit(f"--mesh {args.mesh}: only {len(devs)} "
                             "devices available")
        mesh = jax.sharding.Mesh(np.array(devs[:args.mesh]), ("data",))
        ctx = skt.MeshContext(mesh=mesh, axis="data")
        if args.query_path == "collective" and not ctx.divides(args.shards):
            raise SystemExit(
                f"--query-path collective needs --mesh to divide --shards "
                f"(got {args.shards} shards over {args.mesh} devices, "
                "which replicates instead of sharding)")
    elif args.query_path == "collective":
        raise SystemExit("--query-path collective needs --mesh N")

    horizons = [int(x) for x in args.horizons.split(",") if x.strip()]
    spec = dataclasses.replace(PHONE, n_edges=args.edges, n_vertices=1000)
    st = generate(spec, seed=0)
    sk_spec = build_spec(args.sketch, spec.window_size, n_shards=args.shards)
    if args.tenants:
        pool = skt.TenantPool(sk_spec, n_slots=args.tenants)
        server = SketchServer(pool=pool, pipeline=not args.no_pipeline,
                              query_path=args.query_path,
                              prewarm=not args.no_prewarm,
                              horizons=horizons)
    else:
        server = SketchServer(sk_spec, pipeline=not args.no_pipeline,
                              query_path=args.query_path, mesh=mesh,
                              prewarm=not args.no_prewarm,
                              heat_threshold=args.heat_threshold or None,
                              horizons=horizons)

    from repro.engine.insert import TRACE_COUNTS
    traces_before = TRACE_COUNTS["fused"] + TRACE_COUNTS["stacked"]
    t0 = time.time()
    n_batches = 0
    for batch in edge_batches(st, args.ingest_batch):
        if args.tenants:
            # round-robin tenant split of one stream: every tenant sees a
            # time-ordered substream, and each round is one pooled dispatch
            tid = n_batches % args.tenants
            server.ingest_many([(tid, batch)])
        else:
            server.ingest(batch)
        n_batches += 1
        if args.summary_every and n_batches % args.summary_every == 0:
            print(f"[batch {n_batches}] {server.serving_summary()}")
    jax.block_until_ready(jax.tree.leaves(server.state.shards))  # drain pipe
    dt_ing = time.time() - t0
    traces = (TRACE_COUNTS["fused"] + TRACE_COUNTS["stacked"]
              - traces_before)  # measured, not derived: subwindow
    # boundaries inside batches must not add compiles (engine contract);
    # expect <= #distinct bucketed batch shapes
    print(f"ingested {len(st)} edges in {dt_ing:.2f}s "
          f"({len(st) / dt_ing:.0f} edges/s, {n_batches} batches, "
          f"{args.shards} shards"
          + (f", {args.tenants} tenants" if args.tenants else "")
          + f", {traces} engine compiles)")

    rng = np.random.default_rng(1)
    idx = rng.integers(0, len(st), args.requests)
    t0 = time.time()
    reqs = [server.submit("edge", src=int(st.src[i]), la=int(st.src_label[i]),
                          dst=int(st.dst[i]), lb=int(st.dst_label[i]),
                          tenant=(int(i) % args.tenants if args.tenants
                                  else None))
            for i in idx]
    server.flush()
    dt_q = time.time() - t0
    print(f"answered {len(reqs)} edge queries in {dt_q:.2f}s "
          f"({len(reqs) / dt_q:.0f} q/s)")
    print("sample answers:", [r.answer for r in reqs[:8]])

    if horizons:
        # time-sensitive sweep: every horizon in one fused dispatch
        # (DESIGN.md §14) — the answer tightens as the window narrows
        i = int(idx[0])
        q = skt.QueryBatch.edges(int(st.src[i]), int(st.src_label[i]),
                                 int(st.dst[i]), int(st.dst_label[i]),
                                 last=horizons)
        t0 = time.time()
        if args.tenants:
            sw_spec, sw_st = server.pool.handle_of(int(i) % args.tenants)
            sweep = np.asarray(skt.query(sw_spec, sw_st, q))
        else:
            sweep = np.asarray(skt.query(sk_spec, server.state, q,
                                         path=args.query_path))
        dt_s = time.time() - t0
        print(f"horizon sweep (src={int(st.src[i])} dst={int(st.dst[i])}): "
              + ", ".join(f"last={h}: {int(w)}"
                          for h, w in zip(horizons, sweep[:, 0]))
              + f" ({dt_s:.2f}s, one fused dispatch)")

    if args.sketch != "lgs":  # LGS stores no keys: no reversible analytics
        tenant = 0 if args.tenants else None
        t0 = time.time()
        vids, vws = server.top_k("vertex", args.topk, tenant=tenant)
        es, ed, ews = server.top_k("edge", args.topk, tenant=tenant)
        dt_a = time.time() - t0
        vtop = [(int(v), int(w)) for v, w in zip(np.asarray(vids),
                                                 np.asarray(vws)) if v >= 0]
        etop = [((int(a), int(b)), int(w)) for a, b, w in
                zip(np.asarray(es), np.asarray(ed), np.asarray(ews))
                if a >= 0]
        print(f"top-{args.topk} heavy vertices (vid, w): {vtop} "
              + (f"[tenant {tenant}] " if args.tenants else "")
              + f"({dt_a:.2f}s)")
        print(f"top-{args.topk} heavy edges ((src, dst), w): {etop}")

    if args.heat_threshold and not args.tenants:
        rep = server.budget_report()
        splits = server.live_spec.routing.splits \
            if server.live_spec.routing else ()
        print(f"routing: {len(splits)} split keys live; recommended "
              f"splits {len(rep.routing.splits)}; per-shard combined "
              f"load {['%.3f' % f for f in rep.combined]}")


if __name__ == "__main__":
    main()
