#!/usr/bin/env python3
"""Chip smoke test: the LSketch serving path, end to end, on a TPU.

    python3 chip_smoke.py              # one chip (the default)
    python3 chip_smoke.py --chips 4    # the mesh-resident collective path

One chip: a ``SketchServer`` over the ``lsketch`` serving spec widened to
d=1024 (b=512, 1.15 GB of sketch state on the device) ingests the seeded
com-Friendster analog (500k edges, 100k vertices, 20 vertex and 100 edge
labels, two windows) in batches of 4096 with the ingest pipeline on, then
answers a few thousand edge queries, a few hundred vertex and label
queries, top-k vertices and edges, and a ``last=[1, 2, 4, 8]`` horizon
sweep. It checks that

  * the default-path answers are bit-identical to ``path="scan"`` on the
    same state;
  * the state after a 64k-edge prefix is bit-identical between the
    binned (Pallas key walk) and scan ingest paths;
  * every estimate is one-sided: ``est >= truth - pool_lost`` against the
    exact ``GroundTruth`` replay;
  * the run took the paths it claims: the stacked ingest and planes query
    programs were traced, and the compiled ingest program holds the
    ``sketch_insert_walk`` Mosaic kernel.

Four chips (``--chips 4``): a 4-shard spec placed on a 4-device mesh and
served with ``query_path="collective"`` is compared bit for bit (state,
edge / vertex / label answers, a horizon sweep, top-k vertices) with the
same 4-shard stream served on one device, and each shard must live on
its own device. It serves the stream's first 131,072 edges.

Earlier lines are informational. The last line of standard output is one
JSON object, ``{"ok": true, "device": {...}}``, printed only when every
check passed. Without a TPU (or without the repository around this file)
the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
BATCH = 4096
PREFIX = 64 * 1024
WIDTH = 1024
HORIZONS = [1, 2, 4, 8]
EDGE_QUERIES = 4096
VERTEX_QUERIES = 512
MESH_EDGES = 128 * 1024  # each side of the four-chip check ingests it


class SmokeFailure(Exception):
    """A check failed: the run must not report ok."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    log(f"check ok: {what}")


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


# --------------------------------------------------------------------------
# set-up
# --------------------------------------------------------------------------

def serving_spec(n_shards: int):
    """The served ``lsketch`` spec (``build_spec``) at deployment width."""
    from repro.data.stream import COMFS
    from repro.launch.serve_sketch import build_spec
    spec = build_spec("lsketch", COMFS.window_size, n_shards=n_shards)
    return dataclasses.replace(spec, config=spec.config.replace(d=WIDTH))


def host_batches(st, size: int):
    """The stream as numpy-backed EdgeBatches (the host partition reads
    numpy, so no device round trip per batch)."""
    from repro.core.types import EdgeBatch
    fields = ("src", "dst", "src_label", "dst_label", "edge_label", "weight",
              "time")
    for a in range(0, len(st), size):
        yield EdgeBatch(**{f: getattr(st, f)[a:a + size] for f in fields})


def states_equal(a, b) -> bool:
    import jax
    import jax.numpy as jnp
    return all(bool(jnp.array_equal(x, y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


class Oracle:
    """Exact answers for the stream: ``GroundTruth`` for edges, numpy
    bincounts for vertex and label aggregates (same window rule)."""

    def __init__(self, st, k: int):
        from repro.data.stream import GroundTruth
        self.gt = GroundTruth(st.spec, k).insert_stream(st)
        self.st, self.k = st, k
        self.widx = st.time // self.gt.ws

    def live(self, last=None):
        h = self.k if last is None else min(last, self.k)
        return self.widx > self.gt.cur_widx - h

    def edge(self, a, b, le=None, last=None):
        return self.gt.edge_weight(int(a), int(b), le=le, last=last)

    def by(self, key, n, le=None):
        m = self.live()
        if le is not None:
            m = m & (self.st.edge_label == le)
        return np.bincount(key[m], weights=self.st.weight[m],
                           minlength=n).astype(np.int64)


# --------------------------------------------------------------------------
# one chip
# --------------------------------------------------------------------------

def prefix_identity(spec, st) -> None:
    """The 64k-edge prefix, ingested on the binned and the scan path."""
    import jax
    from repro import sketch as skt
    pre = st.slice(0, PREFIX)
    out = {}
    for path in ("pallas", "scan"):
        h = skt.create(spec)
        for b in host_batches(pre, BATCH):
            h = skt.ingest(spec, h, b, path=path)
        jax.block_until_ready(jax.tree.leaves(h.shards))
        out[path] = h
    check(states_equal(out["pallas"].shards, out["scan"].shards),
          f"prefix state: {PREFIX} edges, binned path == scan path "
          "(every leaf bit-identical)")


def kernel_in_ingest_program(spec, state, st) -> bool:
    """Compile the exact stacked-ingest program the server dispatches and
    look for the Mosaic key-walk kernel in it."""
    from repro.sketch.ingest import _ingest_stacked_lsketch, _partition_stack
    batch = next(host_batches(st, BATCH))
    stacked, n_valid = _partition_stack(spec, batch)
    text = _ingest_stacked_lsketch.lower(
        spec.config, None, state.shards, stacked, n_valid, use_pallas=True,
        emit_delta=True).compile().as_text()
    return "sketch_insert_walk" in text


def run_one_chip(args) -> None:
    import jax
    import jax.numpy as jnp
    from repro import sketch as skt
    from repro.core.types import state_bytes
    from repro.data.stream import COMFS, generate
    from repro.engine import insert as eng_insert
    from repro.launch.serve_sketch import SketchServer
    from repro.sketch.query import QUERY_TRACE_COUNTS

    spec = serving_spec(1)
    cfg = spec.config
    log(f"spec: kind={spec.kind} shards={spec.n_shards} d={cfg.d} "
        f"b={cfg.b} F={cfg.F} r={cfg.r} s={cfg.s} c={cfg.c} k={cfg.k} "
        f"n_blocks={cfg.n_blocks} window={cfg.window_size} "
        f"pool={cfg.pool_capacity}/{cfg.pool_probes}")
    log(f"state_bytes per shard: {state_bytes(cfg)}")
    log(f"insert path: {eng_insert.resolve_path(cfg)}, query path: "
        f"{skt.resolve_query_path(spec)}")
    check(eng_insert.resolve_path(cfg) == "pallas"
          and skt.resolve_query_path(spec) == "pallas",
          "the TPU defaults are the binned insert and the planes query")

    st, dt = _timed(lambda: generate(COMFS, seed=args.seed))
    n_windows = (int(st.time.max()) + 1) / cfg.window_size
    log(f"stream: {COMFS.name} {len(st)} edges, {COMFS.n_vertices} vertices, "
        f"{COMFS.n_vertex_labels} vertex / {COMFS.n_edge_labels} edge labels, "
        f"{n_windows:.2f} windows (generated in {dt:.2f}s)")
    widx = st.time // cfg.subwindow_size
    spans = sum(int(widx[a] != widx[min(a + BATCH, len(st)) - 1])
                for a in range(0, len(st), BATCH))
    log(f"batches: {-(-len(st) // BATCH)} of {BATCH}, {spans} span a "
        "subwindow boundary")

    _, dt = _timed(lambda: prefix_identity(spec, st))
    log(f"prefix phase: {dt:.2f}s (incl. compile)")

    stacked0 = eng_insert.TRACE_COUNTS["stacked"]
    server = SketchServer(spec, max_batch=BATCH, pipeline=True)

    def ingest_all():
        for b in host_batches(st, BATCH):
            server.ingest(b)
        jax.block_until_ready(jax.tree.leaves(server.state.shards))
    _, dt = _timed(ingest_all)
    log(f"ingest: {len(st)} edges in {dt:.3f}s incl. compile "
        f"({len(st) / dt:.0f} edges/s), stacked-ingest traces "
        f"{eng_insert.TRACE_COUNTS['stacked'] - stacked0}")
    state = server.state
    lost = int(jnp.sum(state.shards.pool_lost))
    log(f"pool_lost: {lost}, pool entries: "
        f"{int(jnp.sum(state.shards.pool_key[..., 0] >= 0))}")

    oracle, dt = _timed(lambda: Oracle(st, cfg.k))
    log(f"oracle replay: {dt:.2f}s")
    rng = np.random.default_rng(args.seed + 1)

    # ---- edge queries: a few thousand, half with an edge label
    idx = rng.integers(0, len(st), EDGE_QUERIES)
    use_le = rng.random(len(idx)) < 0.5

    def submit_edges():
        reqs = [server.submit(
            "edge", src=int(st.src[i]), la=int(st.src_label[i]),
            dst=int(st.dst[i]), lb=int(st.dst_label[i]),
            le=int(st.edge_label[i]) if u else None)
            for i, u in zip(idx, use_le)]
        server.flush()
        return reqs
    reqs, dt = _timed(submit_edges)
    log(f"edge queries: {len(reqs)} in {dt:.3f}s incl. compile "
        f"({len(reqs) / dt:.0f} q/s)")
    got = np.array([r.answer for r in reqs], np.int64)
    ref = np.zeros_like(got)
    for with_le in (False, True):
        m = use_le == with_le
        sel = idx[m]
        q = skt.QueryBatch.edges(st.src[sel], st.src_label[sel], st.dst[sel],
                                 st.dst_label[sel],
                                 edge_label=st.edge_label[sel] if with_le
                                 else None)
        ref[m] = np.asarray(skt.query(spec, state, q, path="scan"))
    check(np.array_equal(got, ref),
          f"edge answers: default path == scan path ({len(got)} queries)")
    truth = np.array([oracle.edge(st.src[i], st.dst[i],
                                  le=int(st.edge_label[i]) if u else None)
                      for i, u in zip(idx, use_le)], np.int64)
    check(bool(np.all(got >= truth - lost)),
          f"edge oracle bound est >= truth - pool_lost "
          f"(mean over-estimate {np.mean(got - truth):.3f})")

    # ---- vertex queries: out and in, with and without an edge label
    verts = rng.choice(np.unique(st.src), VERTEX_QUERIES // 4)
    les = rng.integers(0, COMFS.n_edge_labels, len(verts))
    vlab = np.zeros(COMFS.n_vertices, np.int32)
    vlab[st.src] = st.src_label
    vlab[st.dst] = st.dst_label
    n_vq = 0
    for direction, key in (("out", st.src), ("in", st.dst)):
        for with_le in (False, True):
            reqs = [server.submit("vertex", v=int(v), lv=int(vlab[v]),
                                  le=int(e) if with_le else None,
                                  direction=direction)
                    for v, e in zip(verts, les)]
            server.flush()
            got = np.array([r.answer for r in reqs], np.int64)
            ref = []
            for a in range(0, len(verts), 32):  # scan reference: small
                q = skt.QueryBatch.vertices(   # batches (it gathers
                    verts[a:a + 32], vlab[verts[a:a + 32]],  # raw counters)
                    edge_label=les[a:a + 32] if with_le else None,
                    direction=direction)
                ref.append(np.asarray(skt.query(spec, state, q,
                                                path="scan")))
            ref = np.concatenate(ref)
            check(np.array_equal(got, ref),
                  f"vertex answers ({direction}, edge label={with_le}): "
                  f"default path == scan path ({len(got)} queries)")
            if with_le:
                truth = np.array([oracle.by(key, COMFS.n_vertices, le=e)[v]
                                  for v, e in zip(verts, les)])
            else:
                truth = oracle.by(key, COMFS.n_vertices)[verts]
            check(bool(np.all(got >= truth - lost)),
                  f"vertex oracle bound ({direction}, edge label={with_le})")
            n_vq += len(reqs)

    # ---- label aggregates: every vertex label, both directions
    labels = np.arange(COMFS.n_vertex_labels)
    for direction, key in (("out", st.src_label), ("in", st.dst_label)):
        reqs = [server.submit("label", lv=int(l), direction=direction)
                for l in labels]
        server.flush()
        got = np.array([r.answer for r in reqs], np.int64)
        ref = np.asarray(skt.query(spec, state, skt.QueryBatch.labels(
            labels, direction=direction), path="scan"))
        check(np.array_equal(got, ref),
              f"label answers ({direction}): default path == scan path")
        truth = oracle.by(key, COMFS.n_vertex_labels)[labels]
        check(bool(np.all(got >= truth - lost)),
              f"label oracle bound ({direction})")
    log(f"vertex queries: {n_vq}, label queries: {2 * len(labels)}")

    # ---- top-k heavy hitters
    for kind in ("vertex", "edge"):
        got, dt = _timed(lambda: server.top_k(kind, 10))
        fn = skt.heavy_vertices if kind == "vertex" else skt.heavy_edges
        ref = fn(spec, state, 10, path="scan")
        check(all(np.array_equal(np.asarray(a), np.asarray(b))
                  for a, b in zip(got, ref)),
              f"top-10 {kind}: default path == scan path ({dt:.2f}s incl. "
              "compile)")
        check(int(np.asarray(got[-1])[0]) > 0, f"top-10 {kind} is non-empty")

    # ---- horizon sweep
    sw = idx[:256]
    q = skt.QueryBatch.edges(st.src[sw], st.src_label[sw], st.dst[sw],
                             st.dst_label[sw], last=HORIZONS)
    got = np.asarray(skt.query(spec, state, q))
    ref = np.asarray(skt.query(spec, state, q, path="scan"))
    check(np.array_equal(got, ref),
          f"horizon sweep last={HORIZONS}: default path == scan path")
    truth = np.array([[oracle.edge(st.src[i], st.dst[i], last=h) for i in sw]
                      for h in HORIZONS])
    check(bool(np.all(got >= truth - lost)), "horizon sweep oracle bound")
    check(bool(np.all(np.diff(got, axis=0) >= 0)),
          "horizon sweep answers grow with the horizon")

    # ---- the path the run took
    traces = dict(QUERY_TRACE_COUNTS)
    log(f"trace counts: stacked ingest {eng_insert.TRACE_COUNTS['stacked']}, "
        f"queries {sorted((f'{k[0]}/{k[1]}', v) for k, v in traces.items())}")
    check(eng_insert.TRACE_COUNTS["stacked"] > 0,
          "ingest ran the stacked dispatch")
    check(traces.get(("edge", "pallas"), 0) > 0
          and traces.get(("vertex", "pallas"), 0) > 0,
          "queries ran the planes path")
    check(kernel_in_ingest_program(spec, state, st),
          "the compiled ingest program holds the sketch_insert_walk kernel")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'n/a')}")


# --------------------------------------------------------------------------
# four chips
# --------------------------------------------------------------------------

def serve_side(spec, st, idx, **server_kw) -> dict:
    """Serve the stream on one side of the four-chip comparison: ingest
    it, answer edge / vertex / label requests, a horizon sweep and top-10
    vertices. Returns the answers (on the host) and the served state."""
    import jax
    from repro import sketch as skt
    from repro.data.stream import COMFS
    from repro.launch.serve_sketch import SketchServer

    server = SketchServer(spec, max_batch=BATCH, pipeline=True, **server_kw)
    name = "mesh" if "mesh" in server_kw else "one-device"

    def ingest_all():
        for b in host_batches(st, BATCH):
            server.ingest(b)
        jax.block_until_ready(jax.tree.leaves(server.state.shards))
    _, dt = _timed(ingest_all)
    log(f"{name}: ingest {len(st)} edges in {dt:.3f}s incl. compile "
        f"({len(st) / dt:.0f} edges/s)")

    reqs = [server.submit("edge", src=int(st.src[i]), la=int(st.src_label[i]),
                          dst=int(st.dst[i]), lb=int(st.dst_label[i]),
                          le=int(st.edge_label[i]) if i % 2 else None)
            for i in idx]
    reqs += [server.submit("vertex", v=int(st.src[i]),
                           lv=int(st.src_label[i]), direction=d)
             for i in idx[:128] for d in ("out", "in")]
    reqs += [server.submit("label", lv=int(l), direction="out")
             for l in range(COMFS.n_vertex_labels)]
    _, dt = _timed(server.flush)
    log(f"{name}: {len(reqs)} queries in {dt:.3f}s incl. compile")
    sw = idx[:256]
    q = skt.QueryBatch.edges(st.src[sw], st.src_label[sw], st.dst[sw],
                             st.dst_label[sw], last=HORIZONS)
    # vertex top-k only: the edge ranking's variadic sort is minutes of
    # compile per program, paid twice on four chips' time
    return {"state": server.state,
            "answers": np.array([r.answer for r in reqs], np.int64),
            "sweep": np.asarray(skt.query(spec, server.state, q,
                                          path=server.query_path)),
            "top_k": [np.asarray(x) for x in server.top_k("vertex", 10)]}


def run_four_chips(args) -> None:
    import gc
    import jax
    from jax.sharding import Mesh
    from repro.core.types import state_bytes
    from repro.data.stream import COMFS, generate
    from repro.sketch.query import QUERY_TRACE_COUNTS

    devs = jax.devices()
    check(len(devs) >= 4, f"four devices present (found {len(devs)})")
    spec = serving_spec(4)
    cfg = spec.config
    log(f"spec: kind={spec.kind} shards={spec.n_shards} d={cfg.d} b={cfg.b} "
        f"c={cfg.c} k={cfg.k}; state_bytes per shard {state_bytes(cfg)}")
    st = generate(COMFS, seed=args.seed)
    st = st.slice(0, MESH_EDGES)
    log(f"stream: {COMFS.name} first {len(st)} edges")
    idx = np.random.default_rng(args.seed + 1).integers(0, len(st),
                                                        EDGE_QUERIES)

    # one side at a time: the one-device reference holds all four shards
    # (4.6 GB) plus its ingest program's temporaries on device 0, which
    # leaves no room for the mesh side's shard and programs beside it
    mesh = Mesh(np.array(devs[:4]), ("data",))
    a = serve_side(spec, st, idx, mesh=mesh, query_path="collective")
    check(QUERY_TRACE_COUNTS.get(("edge", "collective"), 0) > 0,
          "the mesh side queried on the collective path")
    for leaf in (a["state"].shards.C, a["state"].shards.P,
                 a["state"].shards.key):
        owners = {sh.device for sh in leaf.addressable_shards}
        rows = {(sh.index[0].start, sh.index[0].stop)
                for sh in leaf.addressable_shards}
        check(len(owners) == 4 and len(rows) == 4,
              f"state leaf {tuple(leaf.shape)} is spread one shard per "
              "device over 4 devices")
    a["state"] = jax.device_get(a["state"].shards)
    jax.clear_caches()  # drop the mesh side's programs with its buffers
    gc.collect()
    b = serve_side(spec, st, idx)
    b["state"] = jax.device_get(b["state"].shards)

    check(all(np.array_equal(x, y) for x, y in zip(
        jax.tree.leaves(a["state"]), jax.tree.leaves(b["state"]))),
        "mesh state == one-device state (every leaf bit-identical)")
    check(np.array_equal(a["answers"], b["answers"]),
          f"collective answers == one-device answers "
          f"({len(a['answers'])} queries)")
    check(np.array_equal(a["sweep"], b["sweep"]),
          f"collective horizon sweep last={HORIZONS} == one-device sweep")
    check(all(np.array_equal(x, y) for x, y in zip(a["top_k"], b["top_k"])),
          "collective top-10 vertex == one-device top-10 vertex")
    for i, d in enumerate(devs[:4]):
        stats = d.memory_stats() or {}
        log(f"device {i} peak_bytes_in_use: "
            f"{stats.get('peak_bytes_in_use', 'n/a')}")


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the serving path on one chip; 4: only the "
                         "mesh-resident collective path and its "
                         "one-device reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the repository is missing next to this script "
              f"({e})", file=sys.stderr)
        return 2
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform} "
              "devices only; there is no CPU fallback", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    log(f"devices: {len(devs)} x {devs[0].device_kind}")

    t0 = time.perf_counter()
    try:
        (run_four_chips if args.chips == 4 else run_one_chip)(args)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"total: {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
