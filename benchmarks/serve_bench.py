"""Concurrent multi-tenant serving benchmark (DESIGN.md §11).

Two row families, both same-run A/B'd (``check_bench.py`` gates them):

  * ``tenant_dispatch_throughput`` — the isolated dispatch story: T
    tenants' ingest rounds (and query batches) issued as **one** pooled
    ``TenantPool`` dispatch vs T independent single-tenant handle
    dispatches of the identical data. The pooled rows answer bit-identically
    (tests/test_tenant_pool.py), so the comparison is pure dispatch
    economics: one jitted program over ``[T * n_shards]`` rows vs T
    program launches.

  * ``concurrent_serve_throughput`` — the sustained mixed-traffic story:
    a multi-client driver (real threads enqueueing interleaved ingest +
    query ops with per-op timestamps) drained by a serving loop that is
    either one pool-mode ``SketchServer`` (cross-tenant rounds collapse
    into single pooled dispatches) or T independent ``SketchServer``s.
    Emits edges/s, queries/s, and the p50/p99 **sojourn** latency of query
    ops (enqueue -> answered, the number a client actually experiences),
    pooled and independent, from the same run.

``python -m benchmarks.serve_bench [--quick]`` merges rows into
``BENCH_engine.json``; ``kernel_bench`` runs it as part of the full and
``--only-query`` sweeps so the conformance CI job gates it.
"""

from __future__ import annotations

import argparse
import queue
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import EdgeBatch, LSketchConfig

from .common import merge_bench, timed_medians, write_csv

# small per-tenant sketch: the many-tenants regime is lots of modest
# sketches, not one giant one (pool scan kept small so the dispatch story
# isn't diluted by [B, Q] pool-walk compute)
_CFG = LSketchConfig(d=64, n_blocks=2, F=512, r=4, s=4, c=4, k=4,
                     window_size=400, pool_capacity=512, pool_probes=8)


def _mk_batch(rng, n, t_lo=0, t_hi=99, zipf_a=None):
    if zipf_a:
        # power-law endpoints (the skewed-traffic serving row): same
        # Zipf machinery as the corpus + the kernel_bench skew rows
        from repro.data.tokens import zipf_unigram
        p = zipf_unigram(400, zipf_a)
        src, dst = rng.choice(400, size=n, p=p), rng.choice(400, size=n, p=p)
    else:
        src, dst = rng.integers(0, 400, n), rng.integers(0, 400, n)
    return EdgeBatch(
        src=jnp.asarray(src, jnp.int32),
        dst=jnp.asarray(dst, jnp.int32),
        src_label=jnp.asarray(rng.integers(0, 8, n), jnp.int32),
        dst_label=jnp.asarray(rng.integers(0, 8, n), jnp.int32),
        edge_label=jnp.asarray(rng.integers(0, 4, n), jnp.int32),
        weight=jnp.asarray(np.ones(n), jnp.int32),
        time=jnp.asarray(np.sort(rng.integers(t_lo, t_hi, n)), jnp.int32))


def tenant_dispatch_throughput(T=8, n_per_tenant=2048, q=16, n_shards=1):
    """Pooled vs independent dispatch A/B on identical per-tenant data.

    ``q`` defaults to the many-small-tenants regime the pool targets:
    serving drains hand each tenant a handful of query rows, so the
    independent baseline pays T dispatch overheads on tiny batches while
    the pool pays one ``[T, bucket(q)]`` dispatch of the same total
    probe work (the grouped dispatch — each tenant's block answers only
    its own rows).

    Rows (``_x{T}`` suffixed, scan path — the CPU-CI reference; the same
    single-dispatch collapse carries to the kernel path):

      * ``tenant_pool_ingest_x{T}`` / ``tenant_independent_ingest_x{T}``
        — T tenants' batches as one pooled round vs T handle ingests;
      * ``tenant_pool_query_x{T}`` / ``tenant_independent_query_x{T}``
        — T tenants' query batches as one ``query_many`` dispatch vs T
        ``skt.query`` calls.
    """
    from repro import sketch as skt

    spec = skt.make_spec("lsketch", n_shards=n_shards, config=_CFG)
    rng = np.random.default_rng(0)
    batches = {t: _mk_batch(rng, n_per_tenant) for t in range(T)}
    warmup, iters = 1, 5

    # ingest donates its input handle: pre-create one pool / one handle
    # set per timed call so the A/B times ingest, not state zeroing
    pools = [skt.TenantPool(spec, n_slots=T)
             for _ in range(warmup + iters)]
    inds = [[skt.create(spec) for _ in range(T)]
            for _ in range(warmup + iters)]

    def run_pool_ingest():
        p = pools.pop()
        p.submit(list(batches.items()))
        st = p.flush()
        jax.block_until_ready(st.shards.C)

    def run_ind_ingest():
        hs = inds.pop()
        outs = [skt.ingest(spec, hs[t], batches[t], path="scan")
                for t in range(T)]
        jax.block_until_ready([o.shards.C for o in outs])

    med_ing = timed_medians(
        [("tenant_pool_ingest", run_pool_ingest),
         ("tenant_independent_ingest", run_ind_ingest)],
        warmup=warmup, iters=iters)

    # query A/B on one ingested lineage of the same data
    pool = skt.TenantPool(spec, n_slots=T)
    pool.submit(list(batches.items()))
    pool.flush()
    handles = {t: skt.ingest(spec, skt.create(spec), batches[t], path="scan")
               for t in range(T)}
    qbs = {}
    for t in range(T):
        vs = jnp.asarray(rng.integers(0, 400, q), jnp.int32)
        qbs[t] = skt.QueryBatch.vertices(vs, (vs % 8).astype(jnp.int32),
                                         direction="out")

    def run_pool_query():
        outs = pool.query_many([(t, qbs[t]) for t in range(T)], path="scan")
        jax.block_until_ready(outs)

    def run_ind_query():
        outs = [skt.query(spec, handles[t], qbs[t], path="scan")
                for t in range(T)]
        jax.block_until_ready(outs)

    med_q = timed_medians(
        [("tenant_pool_query", run_pool_query),
         ("tenant_independent_query", run_ind_query)],
        warmup=warmup, iters=7)

    rows, result = [], {}
    n_edges = T * n_per_tenant
    for tag in ("tenant_pool_ingest", "tenant_independent_ingest"):
        dt = med_ing[tag]
        rows.append([f"{tag}_x{T}", T, n_edges, n_shards,
                     f"{dt / n_edges * 1e6:.3f}", f"{dt:.4f}"])
        result[f"{tag}_x{T}"] = {
            "tenants": T, "edges": n_edges, "shards_per_tenant": n_shards,
            "us_per_edge": dt / n_edges * 1e6, "total_s": dt}
    n_q = T * q
    for tag in ("tenant_pool_query", "tenant_independent_query"):
        dt = med_q[tag]
        rows.append([f"{tag}_x{T}", T, n_q, n_shards,
                     f"{dt / n_q * 1e6:.3f}", f"{dt:.4f}"])
        result[f"{tag}_x{T}"] = {
            "tenants": T, "queries": n_q, "shards_per_tenant": n_shards,
            "us_per_query": dt / n_q * 1e6, "total_s": dt}
    write_csv("tenant_dispatch_throughput",
              ["impl", "tenants", "items", "shards", "us_per_item",
               "total_s"], rows)
    merge_bench(result)
    return rows


def _client_ops(rng, T, rounds, edges_per_op, queries_per_op, q_rows,
                zipf_a=None):
    """One client's op script: each round interleaves one ingest op and
    ``queries_per_op`` query ops, round-robin across tenants."""
    ops = []
    for r in range(rounds):
        tid = int(rng.integers(0, T))
        ops.append({"kind": "ingest", "tenant": tid,
                    "batch": _mk_batch(rng, edges_per_op, zipf_a=zipf_a)})
        for _ in range(queries_per_op):
            t2 = int(rng.integers(0, T))
            vs = rng.integers(0, 400, q_rows).astype(np.int32)
            ops.append({"kind": "query", "tenant": t2, "v": vs,
                        "lv": (vs % 8).astype(np.int32)})
    return ops


def _drive(make_server, client_ops, T):
    """Run one serving pass: client threads enqueue timestamped ops; the
    serving loop drains whatever has arrived, applies ingests as one
    cross-tenant round, answers queries grouped per drain. Returns
    (wall seconds, edges, queries, query sojourn latencies [s])."""
    srv_ingest, srv_query, srv_drain = make_server()
    inbox: queue.Queue = queue.Queue()

    def client(ops):
        for op in ops:
            inbox.put((time.perf_counter(), op))
            time.sleep(0)  # yield: interleave with the serving loop

    threads = [threading.Thread(target=client, args=(ops,))
               for ops in client_ops]
    total = sum(len(ops) for ops in client_ops)
    t0 = time.perf_counter()
    for th in threads:
        th.start()
    served, n_edges, n_queries = 0, 0, 0
    latencies = []
    while served < total:
        drained = [inbox.get()]
        while True:
            try:
                drained.append(inbox.get_nowait())
            except queue.Empty:
                break
        ing = [(op["tenant"], op["batch"])
               for _, op in drained if op["kind"] == "ingest"]
        qs = [(ts, op) for ts, op in drained if op["kind"] == "query"]
        if ing:
            srv_ingest(ing)
            n_edges += sum(int(b.src.shape[0]) for _, b in ing)
        if qs:
            srv_query([op for _, op in qs])
            done = time.perf_counter()
            latencies.extend(done - ts for ts, _ in qs)
            n_queries += sum(len(op["v"]) for _, op in qs)
        served += len(drained)
    srv_drain()
    dt = time.perf_counter() - t0
    for th in threads:
        th.join()
    return dt, n_edges, n_queries, latencies


def _prewarm_shapes(srv_ingest, srv_query, T, clients, edges_per_op,
                    queries_per_op, q_rows, rng):
    """Compile every pad-bucket shape a drain can plausibly hit before the
    clock starts: ingest rounds of 1..2*clients batches (distinct and
    same-tenant — same-tenant concatenation grows the per-slot bucket) and
    per-tenant query runs of 1..clients*queries_per_op ops. Run inside
    ``make_server`` (untimed): a mid-run recompile would otherwise land in
    the sojourn tail and report as a phantom p99."""
    srv_ingest([(t % T, _mk_batch(rng, edges_per_op))
                for t in range(max(2, clients))])
    for k in range(1, 2 * clients + 1):
        # same-tenant pileups concatenate per slot: every per-slot count a
        # drain can reach must have its pad bucket compiled
        srv_ingest([(0, _mk_batch(rng, edges_per_op)) for _ in range(k)])
    for m in range(1, clients * queries_per_op + 1):
        ops = []
        for _ in range(m):
            vs = rng.integers(0, 400, q_rows).astype(np.int32)
            ops.append({"tenant": 0, "v": vs,
                        "lv": (vs % 8).astype(np.int32)})
        srv_query(ops)


def concurrent_serve_throughput(T=8, clients=4, rounds=6, edges_per_op=512,
                                queries_per_op=4, q_rows=64, n_shards=1,
                                zipf_a=None,
                                variants=("pooled", "independent"),
                                suffix=""):
    """Sustained mixed ingest+query traffic from ``clients`` concurrent
    client threads over T tenants: one pool-mode ``SketchServer`` (every
    drain's ingests -> one pooled round, every drain's queries -> one
    pooled group dispatch) vs T independent servers (per-tenant dispatch
    fan-out). Emits throughput (edges/s, queries/s) and query sojourn
    p50/p99 rows for both variants, same-run. ``zipf_a`` makes the ingest
    endpoints power-law (the skewed-traffic row — ``--zipf-a``; ``suffix``
    tags its rows, e.g. ``tenant_serve_pooled_zipf_x8``); ``variants``
    restricts the run (a single-variant run is timed alone — only the
    latency sanity checks apply, not an A/B gate)."""
    from repro import sketch as skt
    from repro.launch.serve_sketch import SketchServer

    spec = skt.make_spec("lsketch", n_shards=n_shards, config=_CFG)
    rng = np.random.default_rng(1)
    scripts = [_client_ops(np.random.default_rng(100 + c), T, rounds,
                           edges_per_op, queries_per_op, q_rows,
                           zipf_a=zipf_a)
               for c in range(clients)]

    def make_pooled():
        pool = skt.TenantPool(spec, n_slots=T)
        srv = SketchServer(pool=pool, query_path="scan", prewarm=False)

        def ingest(pairs):
            srv.ingest_many(pairs)

        def query(ops):
            for op in ops:
                for v, lv in zip(op["v"], op["lv"]):
                    srv.submit("vertex", tenant=op["tenant"], v=int(v),
                               lv=int(lv))
            srv.flush()

        def drain():
            jax.block_until_ready(jax.tree.leaves(srv.state.shards))

        _prewarm_shapes(ingest, query, T, clients, edges_per_op,
                        queries_per_op, q_rows, np.random.default_rng(7))
        return ingest, query, drain

    def make_independent():
        srvs = {t: SketchServer(spec, query_path="scan", prewarm=False)
                for t in range(T)}

        def ingest(pairs):
            for t, b in pairs:
                srvs[t].ingest(b)

        def query(ops):
            touched = set()
            for op in ops:
                touched.add(op["tenant"])
                for v, lv in zip(op["v"], op["lv"]):
                    srvs[op["tenant"]].submit("vertex", v=int(v), lv=int(lv))
            for t in sorted(touched):
                srvs[t].flush()

        def drain():
            jax.block_until_ready(
                [jax.tree.leaves(s.state.shards) for s in srvs.values()])

        _prewarm_shapes(ingest, query, T, clients, edges_per_op,
                        queries_per_op, q_rows, np.random.default_rng(7))
        return ingest, query, drain

    warmup, iters = 1, 5
    stats = {key: [] for key in variants}
    makers = {"pooled": make_pooled, "independent": make_independent}

    def run(tag, make):
        out = _drive(make, scripts, T)
        stats[tag].append(out)

    # timed_medians supplies the alternation discipline; the reported time
    # is _drive's own clock (serving only — server construction and shape
    # prewarm excluded, identically for both variants)
    timed_medians(
        [(f"tenant_serve_{key}{suffix}",
          (lambda k: lambda: run(k, makers[k]))(key)) for key in variants],
        warmup=warmup, iters=iters)

    rows, result = [], {}
    for key in variants:
        tag = f"tenant_serve_{key}{suffix}"
        runs = stats[key][warmup:]
        dt = float(np.median([r[0] for r in runs]))
        n_edges = runs[0][1]
        n_queries = runs[0][2]
        lat = np.concatenate([np.asarray(r[3]) for r in runs]) * 1e3
        p50 = float(np.percentile(lat, 50))
        p99 = float(np.percentile(lat, 99))
        rows.append([f"{tag}_x{T}", T, clients, n_edges, n_queries,
                     f"{n_edges / dt:.0f}", f"{n_queries / dt:.0f}",
                     f"{p50:.2f}", f"{p99:.2f}", f"{dt:.4f}"])
        result[f"{tag}_x{T}"] = {
            "tenants": T, "clients": clients, "edges": n_edges,
            "queries": n_queries, "edges_per_s": n_edges / dt,
            "queries_per_s": n_queries / dt, "ms_q_p50": p50,
            "ms_q_p99": p99, "total_s": dt}
    write_csv("concurrent_serve_throughput",
              ["impl", "tenants", "clients", "edges", "queries", "edges_s",
               "queries_s", "ms_q_p50", "ms_q_p99", "total_s"], rows)
    merge_bench(result)
    return rows


def run_all(quick: bool = False, zipf_a: float = 1.5):
    rows = tenant_dispatch_throughput(
        T=8, n_per_tenant=512 if quick else 2048, q=16)
    print("impl,tenants,items,shards,us_per_item,total_s")
    for r in rows:
        print(",".join(str(x) for x in r))
    rows = concurrent_serve_throughput(
        T=8, clients=4, rounds=3 if quick else 6,
        edges_per_op=256 if quick else 512,
        queries_per_op=3 if quick else 4, q_rows=32 if quick else 64)
    print("impl,tenants,clients,edges,queries,edges_s,queries_s,"
          "ms_q_p50,ms_q_p99,total_s")
    for r in rows:
        print(",".join(str(x) for x in r))
    # skewed-traffic serving row (DESIGN.md §13): same driver, power-law
    # ingest endpoints — pooled only (the pooled-vs-independent A/B is the
    # uniform pair above; this row tracks latency health under skew)
    rows = concurrent_serve_throughput(
        T=8, clients=4, rounds=3 if quick else 6,
        edges_per_op=256 if quick else 512,
        queries_per_op=3 if quick else 4, q_rows=32 if quick else 64,
        zipf_a=zipf_a, variants=("pooled",), suffix="_zipf")
    for r in rows:
        print(",".join(str(x) for x in r))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke sizes (seconds, not minutes)")
    ap.add_argument("--zipf-a", type=float, default=1.5,
                    help="Zipf exponent for the skewed-traffic serving "
                         "row (tenant_serve_pooled_zipf_x8)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run_all(quick=args.quick, zipf_a=args.zipf_a)


if __name__ == "__main__":
    main()
