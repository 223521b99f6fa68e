"""Paper reproduction benchmarks — one function per table/figure.

  fig14_are_vs_d        — vertex-query ARE vs matrix width d (paper Fig.14)
  fig15_query_accuracy  — vertex/edge/path/subgraph ARE, LSketch vs GSS/LGS
                          without sliding windows (paper Fig.15)
  fig16_windowed        — same with sliding windows (paper Fig.16)
  tab3_throughput       — insertion time per edge / total (paper Tab.3/4)
  tab5_query_latency    — query response time, sketch vs raw-data scan
                          (paper Tab.5)

Each writes a CSV under experiments/bench/ and returns rows for the runner.
Datasets are the scaled synthetic analogs in repro.data.stream (real hosts
offline; statistics per paper Table 2).
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from repro.core import GSS, LGS, LSketch, LSketchConfig
from repro.data.stream import SPECS, GroundTruth, generate

from .common import are, timer, write_csv


def _dataset(name: str, n_edges: int | None = None, seed: int = 0):
    spec = SPECS[name]
    if n_edges:
        spec = dataclasses.replace(spec, n_edges=n_edges)
    return spec, generate(spec, seed=seed)


def _lsk_cfg(spec, d, k=1, window=False, c=16, F=1024, pool=16384):
    return LSketchConfig(
        d=d, n_blocks=max(1, min(4, spec.n_vertex_labels)), F=F, r=8, s=8,
        c=c, k=k if window else 1,
        window_size=spec.window_size if window else 0,
        pool_capacity=pool, pool_probes=16)


def _build_lsketch(cfg, st):
    sk = LSketch(cfg)
    sk.insert(st.src, st.dst, st.src_label, st.dst_label, st.edge_label,
              st.weight, st.time)
    return sk


def _query_sets(st, gt, n=300, rng=None):
    rng = rng or np.random.default_rng(1)
    idx = rng.integers(0, len(st), n)
    edges = [(int(st.src[i]), int(st.src_label[i]), int(st.dst[i]),
              int(st.dst_label[i]), int(st.edge_label[i])) for i in idx]
    verts = list({(e[0], e[1]) for e in edges})[:n // 2]
    return edges, verts


def fig14_are_vs_d(n_edges=6000, widths=(16, 24, 32, 48, 64, 96, 128)):
    """Vertex-query ARE vs matrix width on the phone dataset (Fig. 14a)."""
    spec, st = _dataset("phone", n_edges)
    gt = GroundTruth(spec, k=1, no_window=True).insert_stream(st)
    edges, verts = _query_sets(st, gt)
    rows = []
    for d in widths:
        cfg = _lsk_cfg(spec, d, F=256)  # small F per paper ("to show the
        # performance difference more clearly, we set a small fingerprint")
        sk = _build_lsketch(cfg, st)
        ests, trus, ests_l, trus_l = [], [], [], []
        for v, lv in verts:
            ests.append(sk.vertex_weight(v, lv))
            trus.append(gt.vertex_weight(v, last=None))
            ests_l.append(sk.vertex_weight(v, lv, le=1))
            trus_l.append(gt.vertex_weight(v, le=1))
        r = are(np.array(ests), np.array(trus))
        rl = are(np.array(ests_l), np.array(trus_l))
        rows.append(["phone", d, f"{r:.5f}", f"{rl:.5f}"])
    write_csv("fig14_are_vs_d", ["dataset", "d", "are", "are_lbl"], rows)
    return rows


def fig15_query_accuracy(datasets=("phone", "road", "enron"), n_edges=6000):
    """Vertex/edge/path/subgraph accuracy for LSketch vs GSS vs LGS."""
    rows = []
    for name in datasets:
        spec, st = _dataset(name, n_edges)
        gt = GroundTruth(spec, k=1, no_window=True).insert_stream(st)
        edges, verts = _query_sets(st, gt, n=200)
        d = {"phone": 64, "road": 48, "enron": 128}[name]
        sk = _build_lsketch(_lsk_cfg(spec, d), st)
        g = GSS(d=d).insert(st.src, st.dst, weight=st.weight)
        l = LGS(d=max(16, d // 2), copies=6, c=16, k=1).insert(
            st.src, st.dst, st.src_label, st.dst_label, st.edge_label,
            st.weight, np.zeros(len(st), np.int32))

        # vertex queries (out-weight)
        for meth, q in (("lsketch", lambda v, lv: sk.vertex_weight(v, lv)),
                        ("gss", lambda v, lv: g.vertex_weight(v, 0)),
                        ("lgs", lambda v, lv: l.vertex_weight(v, lv))):
            est = np.array([q(v, lv) for v, lv in verts])
            tru = np.array([gt.vertex_weight(v) for v, _ in verts])
            rows.append([name, "vertex", meth, f"{are(est, tru):.5f}"])
        # vertex with edge-label restriction (GSS cannot)
        for meth, q in (("lsketch", lambda v, lv: sk.vertex_weight(v, lv, le=1)),
                        ("lgs", lambda v, lv: l.vertex_weight(v, lv, le=1))):
            est = np.array([q(v, lv) for v, lv in verts])
            tru = np.array([gt.vertex_weight(v, le=1) for v, _ in verts])
            rows.append([name, "vertex_lbl", meth, f"{are(est, tru):.5f}"])
        # edge queries
        for meth, q in (("lsketch", lambda e: sk.edge_weight(e[0], e[1], e[2], e[3])),
                        ("gss", lambda e: g.edge_weight(e[0], 0, e[2], 0)),
                        ("lgs", lambda e: l.edge_weight(e[0], e[1], e[2], e[3]))):
            est = np.array([q(e) for e in edges])
            tru = np.array([gt.edge_weight(e[0], e[2]) for e in edges])
            rows.append([name, "edge", meth, f"{are(est, tru):.5f}"])
        # path queries: accuracy = 1 - false positive rate
        rng = np.random.default_rng(3)
        pairs = [(int(st.src[i]), int(st.src_label[i]),
                  int(st.dst[j]), int(st.dst_label[j]))
                 for i, j in zip(rng.integers(0, len(st), 30),
                                 rng.integers(0, len(st), 30))]
        for meth, q in (("lsketch", lambda p: sk.reachable(*p, max_hops=6)),
                        ("gss", lambda p: g.reachable(p[0], 0, p[2], 0, max_hops=6)),
                        ("lgs", lambda p: l.reachable(*p, max_hops=6))):
            fp = 0
            neg = 0
            for p in pairs:
                true = gt.reachable(p[0], p[2], max_hops=6)
                if not true:
                    neg += 1
                    fp += bool(q(p))
            acc = 1.0 - (fp / max(1, neg))
            rows.append([name, "path", meth, f"{acc:.5f}"])
        # subgraph queries (GSS base version unsupported, per paper)
        sub_est, sub_tru = [], []
        for i in range(0, 60, 3):
            es = edges[i:i + 3]
            sub_est.append(sk.subgraph_count(
                [(e[0], e[1], e[2], e[3]) for e in es]))
            sub_tru.append(gt.subgraph_count(
                [(e[0], e[2], None) for e in es]))
        rows.append([name, "subgraph", "lsketch",
                     f"{are(np.array(sub_est), np.array(sub_tru)):.5f}"])
        sub_l = [min(l.edge_weight(e[0], e[1], e[2], e[3]) for e in edges[i:i+3])
                 for i in range(0, 60, 3)]
        rows.append([name, "subgraph", "lgs",
                     f"{are(np.array(sub_l), np.array(sub_tru)):.5f}"])
    write_csv("fig15_query_accuracy", ["dataset", "query", "method", "are"],
              rows)
    return rows


def fig16_windowed(datasets=("phone", "road"), n_edges=6000):
    """Query accuracy with sliding windows: LSketch vs LGS (Fig. 16)."""
    rows = []
    for name in datasets:
        spec, st = _dataset(name, n_edges)
        k = max(2, spec.window_size // spec.subwindow_size // 24)
        gt = GroundTruth(spec, k=k).insert_stream(st)
        d = {"phone": 64, "road": 48}[name]
        cfg = _lsk_cfg(spec, d, k=k, window=True)
        sk = _build_lsketch(cfg, st)
        l = LGS(d=max(16, d // 2), copies=6, c=16, k=k,
                window_size=spec.window_size).insert(
            st.src, st.dst, st.src_label, st.dst_label, st.edge_label,
            st.weight, st.time)
        edges, verts = _query_sets(st, gt, n=150)
        for meth, qe, qv in (
                ("lsketch",
                 lambda e: sk.edge_weight(e[0], e[1], e[2], e[3]),
                 lambda v, lv: sk.vertex_weight(v, lv)),
                ("lgs",
                 lambda e: l.edge_weight(e[0], e[1], e[2], e[3]),
                 lambda v, lv: l.vertex_weight(v, lv))):
            est = np.array([qe(e) for e in edges])
            tru = np.array([gt.edge_weight(e[0], e[2]) for e in edges])
            rows.append([name, "edge", meth, f"{are(est, tru):.5f}"])
            est = np.array([qv(v, lv) for v, lv in verts])
            tru = np.array([gt.vertex_weight(v) for v, _ in verts])
            rows.append([name, "vertex", meth, f"{are(est, tru):.5f}"])
        # label-constrained ('lc' series in Fig. 16)
        est = np.array([sk.edge_weight(e[0], e[1], e[2], e[3], le=e[4])
                        for e in edges])
        tru = np.array([gt.edge_weight(e[0], e[2], le=e[4]) for e in edges])
        rows.append([name, "edge_lc", "lsketch", f"{are(est, tru):.5f}"])
    write_csv("fig16_windowed", ["dataset", "query", "method", "are"], rows)
    return rows


def tab3_throughput(datasets=("phone", "road"), n_edges=20000):
    """Insertion throughput (us/edge, total ms) for GSS/LGS/LSketch, plus
    the block-binned insert (its XLA key walk off TPU; structural on
    CPU)."""
    rows = []
    for name in datasets:
        spec, st = _dataset(name, n_edges)
        d = 64

        def run_lsketch():
            cfg = _lsk_cfg(spec, d, k=8, window=True)
            return _build_lsketch(cfg, st)

        def run_gss():
            return GSS(d=d).insert(st.src, st.dst, weight=st.weight)

        def run_lgs():
            return LGS(d=32, copies=6, c=16, k=8,
                       window_size=spec.window_size).insert(
                st.src, st.dst, st.src_label, st.dst_label, st.edge_label,
                st.weight, st.time)

        for meth, fn in (("gss", run_gss), ("lgs", run_lgs),
                         ("lsketch", run_lsketch)):
            dt, _ = timer(fn, warmup=1, iters=2)
            rows.append([name, meth, f"{dt / len(st) * 1e6:.3f}",
                         f"{dt * 1e3:.1f}"])
    write_csv("tab3_throughput",
              ["dataset", "method", "us_per_edge", "total_ms"], rows)
    return rows


def tab5_query_latency(n_edges=20000, batch=512):
    """Query response time: sketch queries vs raw-data scans (Tab. 5).

    raw = an honest linear scan over the stream arrays (the paper's
    raw-data baseline); sketch = the batched jit'd query amortized per
    query (how a production system issues sketch queries)."""
    import jax
    import jax.numpy as jnp
    from repro.core.queries import edge_query, vertex_query

    spec, st = _dataset("phone", n_edges)
    cfg = _lsk_cfg(spec, 64)
    sk = _build_lsketch(cfg, st)
    rng = np.random.default_rng(0)
    idx = rng.integers(0, len(st), batch)
    qs = jnp.asarray(st.src[idx])
    qd = jnp.asarray(st.dst[idx])
    labels = (jnp.asarray(st.src_label[idx]), jnp.asarray(st.dst_label[idx]),
              jnp.asarray(st.edge_label[idx]))

    def sk_edge():
        w, _ = edge_query(cfg, sk.state, qs, qd, labels, False, None)
        jax.block_until_ready(w)

    def sk_vertex():
        w, _ = vertex_query(cfg, sk.state, qs, (labels[0], labels[2]),
                            "out", False, None)
        jax.block_until_ready(w)

    src, dst, w = st.src, st.dst, st.weight

    def raw_edge():
        tot = 0
        for i in range(8):  # 8 queries per timing iter
            tot += int(np.sum(w[(src == int(qs[i])) & (dst == int(qd[i]))]))
        return tot

    def raw_vertex():
        tot = 0
        for i in range(8):
            tot += int(np.sum(w[src == int(qs[i])]))
        return tot

    rows = []
    for qname, sk_fn, raw_fn, raw_n in (
            ("vertex", sk_vertex, raw_vertex, 8),
            ("edge", sk_edge, raw_edge, 8)):
        dt_s, _ = timer(sk_fn, warmup=2, iters=5)
        dt_r, _ = timer(raw_fn, warmup=1, iters=3)
        rows.append([qname, "sketch_batched", f"{dt_s / batch * 1e6:.2f}"])
        rows.append([qname, "raw_scan", f"{dt_r / raw_n * 1e6:.2f}"])
    write_csv("tab5_query_latency", ["query", "method", "us_per_query"], rows)
    return rows
