"""Query-path equivalence + window-plane cache contract (DESIGN.md §8).

The acceptance pin for the kernel read path: ``path="pallas"`` (shard-axis
kernels / compiled XLA lowerings over cached window-reduced planes) must
answer **bit-identically** to ``path="scan"`` (the dense vmapped
reference) across kinds x shard counts x window positions — including
ring wraparound and pool overflow — and the plane cache must never serve
stale planes across ingest / pipelined flush / restore / merge_all.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from conftest import random_stream
from repro import sketch as skt
from repro.core import LSketchConfig
from repro.core.gss import gss_config
from repro.core.types import EdgeBatch
import importlib

q_mod = importlib.import_module("repro.sketch.query")

LS_CFG = LSketchConfig(d=64, n_blocks=2, F=512, r=4, s=4, c=4, k=4,
                       window_size=400, pool_capacity=256, pool_probes=8)
GSS_CFG = gss_config(d=64)


def _batch(arrays) -> EdgeBatch:
    return EdgeBatch(*[jnp.asarray(x, jnp.int32) for x in arrays])


def _stream(seed, n=600, tmax=2400, n_vertices=50):
    return random_stream(np.random.default_rng(seed), n=n, tmax=tmax,
                         n_vertices=n_vertices)


def _query_suite(kind, n_queries=64, seed=7):
    """One batch of every query kind x label restriction x direction."""
    rng = np.random.default_rng(seed)
    qs = rng.integers(0, 60, n_queries).astype(np.int32)
    qd = rng.integers(0, 60, n_queries).astype(np.int32)
    la, lb = (qs % 3).astype(np.int32), (qd % 3).astype(np.int32)
    le = rng.integers(0, 5, n_queries).astype(np.int32)
    vs = np.arange(40, dtype=np.int32)
    lvs = (vs % 3).astype(np.int32)
    lev = rng.integers(0, 5, 40).astype(np.int32)
    lasts = (None,) if kind == "gss" else (None, 1, 2)
    for last in lasts:
        yield skt.QueryBatch.edges(qs, la, qd, lb, last=last)
        yield skt.QueryBatch.edges(qs, la, qd, lb, edge_label=le, last=last)
        for direction in ("out", "in"):
            yield skt.QueryBatch.vertices(vs, lvs, direction=direction,
                                          last=last)
            yield skt.QueryBatch.vertices(vs, lvs, edge_label=lev,
                                          direction=direction, last=last)
            yield skt.QueryBatch.labels(np.arange(4, dtype=np.int32),
                                        direction=direction, last=last)
            yield skt.QueryBatch.labels(
                np.arange(4, dtype=np.int32),
                edge_label=np.arange(4, dtype=np.int32) % 5,
                direction=direction, last=last)


def _assert_paths_agree(spec, state, kind, ctx=""):
    for qb in _query_suite(kind):
        a = np.asarray(skt.query(spec, state, qb, path="scan"))
        b = np.asarray(skt.query(spec, state, qb, path="pallas"))
        assert np.array_equal(a, b), (
            f"{ctx}: scan != pallas for {qb.kind} last={qb.last} "
            f"le={qb.edge_label is not None} dir={qb.direction}: "
            f"{a[:8]} vs {b[:8]}")


# --------------------------------------------------------------------------
# bit-identity sweep: kinds x shards x window positions (incl. wraparound)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kind,ns", [("lsketch", 1), ("lsketch", 4),
                                     ("gss", 1), ("gss", 4)])
def test_query_paths_bit_identical_across_window_positions(kind, ns):
    cfg = LS_CFG if kind == "lsketch" else GSS_CFG
    spec = skt.SketchSpec(kind=kind, config=cfg, n_shards=ns)
    arrays = _stream(seed=11)
    if kind == "gss":
        src, dst, la, lb, le, w, t = arrays
        z = np.zeros_like(la)
        arrays = (src, dst, z, z, z, w, z)
    state = skt.create(spec)
    n = len(arrays[0])
    step = -(-n // 4)
    for stage, a in enumerate(range(0, n, step)):
        chunk = tuple(x[a:a + step] for x in arrays)
        state = skt.ingest(spec, state, _batch(chunk), path="scan")
        _assert_paths_agree(spec, state, kind, ctx=f"{kind} x{ns} s{stage}")


@pytest.mark.parametrize("ns", [1, 4])
def test_query_paths_bit_identical_after_wraparound(ns):
    """Ring wrapped far past the original stream: the planes must reduce to
    the same (mostly-expired) window the dense reference masks."""
    cfg = LS_CFG
    spec = skt.SketchSpec(kind="lsketch", config=cfg, n_shards=ns)
    old = _stream(seed=12, n=200, tmax=cfg.window_size - 1)
    state = skt.ingest(spec, skt.create(spec), _batch(old))
    late = tuple(np.asarray(x, np.int32) for x in
                 ([9999], [0], [9998], [0], [0], [1],
                  [cfg.subwindow_size * 40]))
    state = skt.ingest(spec, state, _batch(late))
    _assert_paths_agree(spec, state, "lsketch", ctx=f"wraparound x{ns}")


@pytest.mark.parametrize("ns", [1, 4])
def test_query_paths_bit_identical_under_pool_overflow(ns):
    """A saturated additional pool (pool_lost > 0) answers identically on
    both paths — the pool planes carry the same window-reduced totals."""
    cfg = LSketchConfig(d=8, n_blocks=2, F=256, r=2, s=2, c=4, k=4,
                        window_size=400, pool_capacity=8, pool_probes=2)
    spec = skt.SketchSpec(kind="lsketch", config=cfg, n_shards=ns)
    arrays = _stream(seed=13, n=500, tmax=1500, n_vertices=400)
    state = skt.ingest(spec, skt.create(spec), _batch(arrays))
    assert int(jnp.sum(state.shards.pool_lost)) > 0, "pool must saturate"
    _assert_paths_agree(spec, state, "lsketch", ctx=f"pool-overflow x{ns}")


# --------------------------------------------------------------------------
# plane-cache invalidation: query -> ingest -> query never serves stale
# --------------------------------------------------------------------------

def _fresh_truth(spec, state, qb):
    """The scan path never caches — it is the staleness oracle."""
    return np.asarray(skt.query(spec, state, qb, path="scan"))


@pytest.mark.parametrize("ns", [1, 4])
def test_plane_cache_never_stale_across_ingest(ns):
    spec = skt.SketchSpec(kind="lsketch", config=LS_CFG, n_shards=ns)
    arrays = _stream(seed=21)
    chunks = [tuple(x[a:a + 150] for x in arrays)
              for a in range(0, len(arrays[0]), 150)]
    qb = skt.QueryBatch.edges(arrays[0][:48], arrays[2][:48],
                              arrays[1][:48], arrays[3][:48])
    state = skt.create(spec)
    for chunk in chunks:
        # query (populates the cache on this handle) ...
        got = np.asarray(skt.query(spec, state, qb, path="pallas"))
        assert np.array_equal(got, _fresh_truth(spec, state, qb))
        # ... then ingest: the new handle must answer with fresh planes
        state = skt.ingest(spec, state, _batch(chunk))
        got = np.asarray(skt.query(spec, state, qb, path="pallas"))
        assert np.array_equal(got, _fresh_truth(spec, state, qb)), \
            "stale planes served after ingest"


def test_plane_cache_never_stale_across_pipelined_flush():
    spec = skt.SketchSpec(kind="lsketch", config=LS_CFG, n_shards=4)
    arrays = _stream(seed=22)
    qb = skt.QueryBatch.vertices(np.arange(30, dtype=np.int32),
                                 np.arange(30, dtype=np.int32) % 3)
    ing = skt.AsyncIngestor(spec)
    for a in range(0, len(arrays[0]), 120):
        ing.submit(_batch(tuple(x[a:a + 120] for x in arrays)))
        st = ing.state  # implicit flush
        got = np.asarray(skt.query(spec, st, qb, path="pallas"))
        assert np.array_equal(got, _fresh_truth(spec, st, qb)), \
            "stale planes served across AsyncIngestor flush"


def test_plane_cache_never_stale_across_restore_and_merge(tmp_path):
    spec = skt.SketchSpec(kind="lsketch", config=LS_CFG, n_shards=4)
    arrays = _stream(seed=23)
    half = len(arrays[0]) // 2
    qb = skt.QueryBatch.edges(arrays[0][:48], arrays[2][:48],
                              arrays[1][:48], arrays[3][:48])
    state = skt.ingest(spec, skt.create(spec),
                       _batch(tuple(x[:half] for x in arrays)))
    np.asarray(skt.query(spec, state, qb, path="pallas"))  # warm the cache
    skt.save(spec, state, tmp_path / "ck")
    state = skt.ingest(spec, state,
                       _batch(tuple(x[half:] for x in arrays)))
    got = np.asarray(skt.query(spec, state, qb, path="pallas"))
    assert np.array_equal(got, _fresh_truth(spec, state, qb))

    # restore rewinds to the checkpoint: fresh handle, fresh planes
    restored = skt.restore(spec, tmp_path / "ck")
    got = np.asarray(skt.query(spec, restored, qb, path="pallas"))
    assert np.array_equal(got, _fresh_truth(spec, restored, qb)), \
        "stale planes served after restore"

    # merge_all decodes to a plain state: the shim query path must also
    # build planes for the merged (not the sharded) counters
    merged = skt.merge_all(spec, restored)
    spec1 = spec.replace(n_shards=1)
    got = np.asarray(skt.query(spec1, merged, qb, path="pallas"))
    assert np.array_equal(got, _fresh_truth(spec1, merged, qb)), \
        "stale planes served after merge_all"


# --------------------------------------------------------------------------
# cache reuse + compile counts: one program per (kind, bucket, path),
# one plane build per (handle, horizon)
# --------------------------------------------------------------------------

def test_plane_cache_reuse_and_horizon_aliasing():
    spec = skt.SketchSpec(kind="lsketch", config=LS_CFG, n_shards=2)
    arrays = _stream(seed=31)
    state = skt.ingest(spec, skt.create(spec), _batch(arrays))
    qb = lambda last: skt.QueryBatch.edges(
        arrays[0][:32], arrays[2][:32], arrays[1][:32], arrays[3][:32],
        last=last)

    before = q_mod.PLANES_BUILD_COUNTS["build"]
    skt.query(spec, state, qb(None), path="pallas")
    assert q_mod.PLANES_BUILD_COUNTS["build"] - before == 1
    # same handle, same horizon: cache hit — no rebuild, any query kind
    skt.query(spec, state, qb(None), path="pallas")
    skt.query(spec, state, skt.QueryBatch.labels([0, 1], last=None),
              path="pallas")
    assert q_mod.PLANES_BUILD_COUNTS["build"] - before == 1
    # last >= k aliases the full-window planes (same validity mask)
    skt.query(spec, state, qb(LS_CFG.k), path="pallas")
    skt.query(spec, state, qb(LS_CFG.k + 3), path="pallas")
    assert q_mod.PLANES_BUILD_COUNTS["build"] - before == 1
    # a tighter horizon is a different pure function -> one more build
    skt.query(spec, state, qb(1), path="pallas")
    assert q_mod.PLANES_BUILD_COUNTS["build"] - before == 2
    # a new handle starts cold
    state2 = skt.ingest(spec, state, _batch(
        tuple(x[:64] for x in _stream(seed=32))))
    skt.query(spec, state2, qb(None), path="pallas")
    assert q_mod.PLANES_BUILD_COUNTS["build"] - before == 3


def test_one_jitted_program_per_kind_bucket_path():
    spec = skt.SketchSpec(kind="lsketch", config=LS_CFG, n_shards=2)
    arrays = _stream(seed=33)
    state = skt.ingest(spec, skt.create(spec), _batch(arrays))

    def edge_q(n):
        return skt.QueryBatch.edges(arrays[0][:n], arrays[2][:n],
                                    arrays[1][:n], arrays[3][:n])

    for path in ("scan", "pallas"):
        before = dict(q_mod.QUERY_TRACE_COUNTS)
        delta = lambda kind: (q_mod.QUERY_TRACE_COUNTS.get((kind, path), 0)
                              - before.get((kind, path), 0))
        skt.query(spec, state, edge_q(20), path=path)  # bucket 32
        skt.query(spec, state, edge_q(27), path=path)  # same bucket
        assert delta("edge") <= 1, \
            f"{path}: same (kind, bucket) retraced"
        skt.query(spec, state, edge_q(40), path=path)  # bucket 64
        n_after_new_bucket = delta("edge")
        skt.query(spec, state, edge_q(33), path=path)  # bucket 64 again
        assert delta("edge") == n_after_new_bucket, \
            f"{path}: repeated bucket retraced"
        skt.query(spec, state, skt.QueryBatch.vertices(
            np.arange(20, dtype=np.int32),
            np.arange(20, dtype=np.int32) % 3), path=path)
        skt.query(spec, state, skt.QueryBatch.vertices(
            np.arange(25, dtype=np.int32),
            np.arange(25, dtype=np.int32) % 3), path=path)
        assert delta("vertex") <= 1, f"{path}: vertex bucket retraced"


def test_clear_plane_cache_forces_rebuild():
    spec = skt.SketchSpec(kind="lsketch", config=LS_CFG, n_shards=1)
    arrays = _stream(seed=34)
    state = skt.ingest(spec, skt.create(spec), _batch(arrays))
    qb = skt.QueryBatch.labels([0, 1, 2])
    a = np.asarray(skt.query(spec, state, qb, path="pallas"))
    before = q_mod.PLANES_BUILD_COUNTS["build"]
    skt.clear_plane_cache(state)
    b = np.asarray(skt.query(spec, state, qb, path="pallas"))
    assert q_mod.PLANES_BUILD_COUNTS["build"] - before == 1
    assert np.array_equal(a, b)


# --------------------------------------------------------------------------
# incremental plane maintenance (DESIGN.md §10): a flush's PlanesDelta
# folded into the parent's cached planes must be bit-identical to a cold
# rebuild, and must fall back whenever the flush moved the ring
# --------------------------------------------------------------------------

def _planes_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _live_batch(seed, n=64, tlo=2300, thi=2400):
    """A single-subwindow batch inside the stream's live subwindow
    (t in [tlo, thi) with subwindow_size=100 -> no ring movement)."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, 50, n).astype(np.int32)
    dst = rng.integers(0, 50, n).astype(np.int32)
    return _batch((src, dst, src % 3, dst % 3, rng.integers(0, 5, n),
                   rng.integers(1, 4, n), np.sort(rng.integers(tlo, thi, n))))


@pytest.mark.parametrize("ns", [1, 4])
def test_planes_delta_bit_identical_every_horizon(ns):
    spec = skt.SketchSpec(kind="lsketch", config=LS_CFG, n_shards=ns)
    state = skt.ingest(spec, skt.create(spec), _batch(_stream(seed=51)))
    for last in (None, 1, 2):  # warm every horizon's cache entry
        skt.query_planes(spec, state, last)

    before = dict(q_mod.PLANES_BUILD_COUNTS)
    state2 = skt.ingest(spec, state, _live_batch(seed=52))
    for last in (None, 1, 2, LS_CFG.k + 5):
        inc = skt.query_planes(spec, state2, last)
        skt.clear_plane_cache(state2)
        cold = skt.query_planes(spec, state2, last)
        assert _planes_equal(inc, cold), \
            f"x{ns} last={last}: delta-applied planes != cold rebuild"
    # every horizon was served by delta apply, never a hidden rebuild
    # (the clear_plane_cache cold builds are the only "build" increments:
    # one per horizon pair above)
    assert q_mod.PLANES_BUILD_COUNTS["delta"] - before["delta"] >= 1
    # query answers ride the delta-applied planes bit-identically
    state3 = skt.ingest(spec, state2, _live_batch(seed=53))
    _assert_paths_agree(spec, state3, "lsketch", ctx=f"delta x{ns}")


@pytest.mark.parametrize("ns", [1, 4])
def test_planes_delta_horizon_gating_on_stale_slot(ns):
    """A late flush into an *older* still-claimed subwindow (no reset, no
    advance) contributes to the full-window planes but not to a horizon
    whose validity mask excludes that slot — same as a cold rebuild."""
    spec = skt.SketchSpec(kind="lsketch", config=LS_CFG, n_shards=ns)
    state = skt.ingest(spec, skt.create(spec), _batch(_stream(seed=54)))
    for last in (None, 1, 2):
        skt.query_planes(spec, state, last)
    # stream tmax=2400 -> cur subwindow idx 23; t in [2200, 2300) is the
    # previous subwindow, slot already claimed at idx 22 -> ok stays True
    state2 = skt.ingest(spec, state, _live_batch(seed=55, tlo=2200,
                                                 thi=2300))
    d0 = q_mod.PLANES_BUILD_COUNTS["delta"]
    for last in (None, 1, 2):
        inc = skt.query_planes(spec, state2, last)
        skt.clear_plane_cache(state2)
        cold = skt.query_planes(spec, state2, last)
        assert _planes_equal(inc, cold), \
            f"x{ns} last={last}: stale-slot delta gating diverged"
    assert q_mod.PLANES_BUILD_COUNTS["delta"] - d0 >= 1
    _assert_paths_agree(spec, state2, "lsketch", ctx=f"stale-slot x{ns}")


def test_planes_delta_fallback_on_ring_movement():
    """Window advance (slot reset) and multi-subwindow batches invalidate
    the delta -> full rebuild, still bit-identical to scan."""
    spec = skt.SketchSpec(kind="lsketch", config=LS_CFG, n_shards=4)
    state = skt.ingest(spec, skt.create(spec), _batch(_stream(seed=56)))
    skt.query_planes(spec, state)

    # advance: t=2400.. claims subwindow 24, resetting a wrapped slot
    before = dict(q_mod.PLANES_BUILD_COUNTS)
    st_adv = skt.ingest(spec, state, _live_batch(seed=57, tlo=2400,
                                                 thi=2450))
    skt.query_planes(spec, st_adv)
    assert q_mod.PLANES_BUILD_COUNTS["build"] == before["build"] + 1
    assert q_mod.PLANES_BUILD_COUNTS["delta"] == before["delta"]
    _assert_paths_agree(spec, st_adv, "lsketch", ctx="advance fallback")

    # multi-subwindow batch: its writes land in two slots, so the delta
    # record is marked invalid -> rebuild
    skt.query_planes(spec, st_adv)
    before = dict(q_mod.PLANES_BUILD_COUNTS)
    st_span = skt.ingest(spec, st_adv, _live_batch(seed=58, tlo=2400,
                                                   thi=2600))
    skt.query_planes(spec, st_span)
    assert q_mod.PLANES_BUILD_COUNTS["build"] == before["build"] + 1
    assert q_mod.PLANES_BUILD_COUNTS["delta"] == before["delta"]


def test_planes_delta_chain_resolution_and_overflow_cap():
    """Several un-queried flushes accumulate a delta chain that resolves
    in one go; past MAX_DELTA_CHAIN the chain is abandoned (bounded host
    memory) and the next query pays one rebuild."""
    spec = skt.SketchSpec(kind="lsketch", config=LS_CFG, n_shards=2)
    state = skt.ingest(spec, skt.create(spec), _batch(_stream(seed=59)))
    skt.query_planes(spec, state)

    before = dict(q_mod.PLANES_BUILD_COUNTS)
    st = state
    for i in range(3):  # three flushes, no query in between
        st = skt.ingest(spec, st, _live_batch(seed=60 + i))
    inc = skt.query_planes(spec, st)
    assert q_mod.PLANES_BUILD_COUNTS["delta"] == before["delta"] + 1
    assert q_mod.PLANES_BUILD_COUNTS["build"] == before["build"]
    skt.clear_plane_cache(st)
    assert _planes_equal(inc, skt.query_planes(spec, st))

    before = dict(q_mod.PLANES_BUILD_COUNTS)
    for i in range(q_mod.MAX_DELTA_CHAIN + 2):
        st = skt.ingest(spec, st, _live_batch(seed=80 + i))
    skt.query_planes(spec, st)
    assert q_mod.PLANES_BUILD_COUNTS["delta"] == before["delta"]
    assert q_mod.PLANES_BUILD_COUNTS["build"] == before["build"] + 1


def test_planes_delta_under_pool_overflow():
    """The additional pool's contribution is linear too: a delta-applied
    flush on a saturated pool matches the cold rebuild bit-for-bit."""
    cfg = LSketchConfig(d=8, n_blocks=2, F=256, r=2, s=2, c=4, k=4,
                        window_size=400, pool_capacity=8, pool_probes=2)
    spec = skt.SketchSpec(kind="lsketch", config=cfg, n_shards=2)
    arrays = _stream(seed=61, n=500, tmax=1500, n_vertices=400)
    state = skt.ingest(spec, skt.create(spec), _batch(arrays))
    assert int(jnp.sum(state.shards.pool_lost)) > 0, "pool must saturate"
    skt.query_planes(spec, state)
    d0 = q_mod.PLANES_BUILD_COUNTS["delta"]
    # live subwindow for tmax=1500 is [1400, 1500); high-degree vertices
    # keep hitting the (full) pool
    rng = np.random.default_rng(62)
    src = rng.integers(0, 400, 64).astype(np.int32)
    dst = rng.integers(0, 400, 64).astype(np.int32)
    b = _batch((src, dst, src % 3, dst % 3, rng.integers(0, 5, 64),
                rng.integers(1, 4, 64),
                np.sort(rng.integers(1400, 1500, 64))))
    state2 = skt.ingest(spec, state, b)
    inc = skt.query_planes(spec, state2)
    assert q_mod.PLANES_BUILD_COUNTS["delta"] == d0 + 1
    skt.clear_plane_cache(state2)
    assert _planes_equal(inc, skt.query_planes(spec, state2))
    _assert_paths_agree(spec, state2, "lsketch", ctx="pool-overflow delta")


def test_async_ingestor_steady_state_builds_stay_flat():
    """Satellite: N pipelined flushes through AsyncIngestor.state (the
    implicit flush) with a query after each — after the first build, the
    cache is maintained purely by delta apply: PLANES_BUILD_COUNTS
    ["build"] must stay flat."""
    spec = skt.SketchSpec(kind="lsketch", config=LS_CFG, n_shards=4)
    qb = skt.QueryBatch.vertices(np.arange(30, dtype=np.int32),
                                 np.arange(30, dtype=np.int32) % 3)
    ing = skt.AsyncIngestor(spec)
    ing.submit(_batch(_stream(seed=63)))
    st = ing.state
    ref = np.asarray(skt.query(spec, st, qb, path="pallas"))  # first build
    before = dict(q_mod.PLANES_BUILD_COUNTS)
    n_flushes = 6
    for i in range(n_flushes):
        ing.submit(_live_batch(seed=64 + i))
        st = ing.state  # implicit flush must propagate planes too
        got = np.asarray(skt.query(spec, st, qb, path="pallas"))
        assert np.array_equal(got, _fresh_truth(spec, st, qb))
    assert q_mod.PLANES_BUILD_COUNTS["build"] == before["build"], \
        "hidden full rebuild during steady-state pipelined serving"
    assert q_mod.PLANES_BUILD_COUNTS["delta"] == \
        before["delta"] + n_flushes


# --------------------------------------------------------------------------
# frontends ride the path selector
# --------------------------------------------------------------------------

def test_object_shim_query_path_parity():
    from repro.core import LSketch
    arrays = _stream(seed=41)
    src, dst, la, lb, le, w, t = arrays
    sk_scan = LSketch(LS_CFG, query_path="scan").insert(*arrays)
    sk_pal = LSketch(LS_CFG, query_path="pallas").insert(*arrays)
    for i in range(0, 40, 7):
        args = (int(src[i]), int(la[i]), int(dst[i]), int(lb[i]))
        assert sk_scan.edge_weight(*args) == sk_pal.edge_weight(*args)
        assert sk_scan.vertex_weight(int(src[i]), int(la[i])) == \
            sk_pal.vertex_weight(int(src[i]), int(la[i]))
    assert sk_scan.label_aggregate(1) == sk_pal.label_aggregate(1)


def test_telemetry_load_vector_path_parity():
    from repro.telemetry.router_sketch import RouterTelemetry
    rng = np.random.default_rng(5)
    counts = rng.integers(0, 4, (256, 16))
    ts, tp = (RouterTelemetry(n_experts=16, query_path=p)
              for p in ("scan", "pallas"))
    for step in range(4):
        ts.ingest(counts, step)
        tp.ingest(counts, step)
    assert np.array_equal(ts.load_vector(), tp.load_vector())
    assert np.array_equal(ts.load_vector(last=2), tp.load_vector(last=2))


def test_sketch_server_query_path_parity():
    from repro.launch.serve_sketch import SketchServer
    arrays = _stream(seed=42, n=300)
    spec = skt.SketchSpec(kind="lsketch", config=LS_CFG, n_shards=4)
    answers = {}
    for path in ("scan", "pallas"):
        srv = SketchServer(spec, query_path=path)
        srv.ingest(_batch(arrays))
        reqs = [srv.submit("edge", src=int(arrays[0][i]),
                           la=int(arrays[2][i]), dst=int(arrays[1][i]),
                           lb=int(arrays[3][i]))
                for i in range(0, 60, 5)]
        srv.flush()
        answers[path] = [r.answer for r in reqs]
    assert answers["scan"] == answers["pallas"]
