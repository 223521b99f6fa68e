"""Functional sharded-sketch handle layer (repro.sketch, ISSUE 2).

The contracts this layer must uphold:

  * shard-equivalence: N-shard hash-partitioned ingest followed by
    ``merge_all`` is bit-identical to single-sketch ingest of the same
    stream (validated by ``shards_compatible``), across window wraparound
    and pool overflow;
  * queries fan through shards and sum — same answers as the single sketch;
  * checkpoints round-trip through ``save``/``restore``, including a
    restore under a *different* shard count;
  * the spec is hashable/jit-static and JSON round-trips;
  * NamedSharding placement leaves results unchanged;
  * the object wrappers are shims: same bits as the functional layer.
"""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from conftest import random_stream
from repro import sketch as skt
from repro.core import (EMPTY, EdgeBatch, LGS, LSketchConfig, init_state)
from repro.core.lsketch import precompute
from repro.engine import insert as eng_insert

CFG = LSketchConfig(d=64, n_blocks=2, F=512, r=4, s=2, c=4, k=4,
                    window_size=400, pool_capacity=32768, pool_probes=8)


def _states_equal(a, b) -> bool:
    return all(bool(jnp.array_equal(x, y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _batch(arrays) -> EdgeBatch:
    return EdgeBatch(*[jnp.asarray(x, jnp.int32) for x in arrays])


def _disjoint_row_srcs(cfg, count):
    """Source entities whose candidate row sets are pairwise disjoint —
    cross-shard matrix contention is then structurally impossible, so the
    equivalence property is exercised on the window/pool machinery rather
    than on hash luck."""
    srcs, used = [], set()
    for v in range(4000):
        lab = v % 3
        pre = precompute(cfg, jnp.asarray([v], jnp.int32),
                         jnp.asarray([lab], jnp.int32))
        pos = (np.asarray(pre.s)[:, None] + np.asarray(pre.offs)) \
            % np.asarray(pre.width)[:, None]
        rows = set((np.asarray(pre.start)[:, None] + pos).ravel().tolist())
        if used & rows:
            continue
        used |= rows
        srcs.append((v, lab))
        if len(srcs) >= count:
            break
    return srcs


def _overflow_stream(cfg, seed=3, n_hot=500, n_cold=1200, tmax=3000):
    """One hot source saturates its probe cells (pool overflow) while cold
    sources spread over shards; timestamps span ~30 subwindows (k=4 ring
    wraps many times)."""
    srcs = _disjoint_row_srcs(cfg, 8)
    rng = np.random.default_rng(seed)
    hot_v, hot_l = srcs[0]
    src = np.concatenate([
        np.full(n_hot, hot_v),
        np.array([srcs[i][0] for i in rng.integers(1, len(srcs), n_cold)]),
    ]).astype(np.int32)
    la = np.concatenate([np.full(n_hot, hot_l),
                         src[n_hot:] % 3]).astype(np.int32)
    n = n_hot + n_cold
    dst = rng.integers(0, 5000, n).astype(np.int32)
    lb = (dst % 3).astype(np.int32)
    le = rng.integers(0, 4, n).astype(np.int32)
    w = rng.integers(1, 4, n).astype(np.int32)
    perm = rng.permutation(n)
    src, la, dst, lb, le, w = (x[perm] for x in (src, la, dst, lb, le, w))
    t = np.sort(rng.integers(0, tmax, n)).astype(np.int32)
    return src, dst, la, lb, le, w, t


# --------------------------------------------------------------------------
# shard equivalence: the acceptance property
# --------------------------------------------------------------------------

def test_shard_equivalence_wraparound_and_pool_overflow():
    arrays = _overflow_stream(CFG)
    batch = _batch(arrays)
    ref = eng_insert.insert_batch(CFG, init_state(CFG), batch, path="scan")
    assert int(jnp.sum(ref.pool_key[:, 0] != EMPTY)) > 0, \
        "stream must overflow into the additional pool"

    spec = skt.make_spec("lsketch", n_shards=4, config=CFG)
    state = skt.ingest(spec, skt.create(spec), batch)
    sizes = np.bincount(skt.shard_assignment(spec, arrays[0], arrays[2]),
                        minlength=4)
    assert (sizes > 0).all(), "every shard must receive traffic"
    assert bool(skt.shards_compatible(spec, state))
    merged = skt.merge_all(spec, state)
    assert _states_equal(ref, merged)


def test_shard_equivalence_incremental_batches():
    """Feeding the stream as many sharded ingest calls == one call == the
    single sketch (ring claims compose across dispatch boundaries)."""
    arrays = _overflow_stream(CFG, seed=4, n_hot=300, n_cold=900)
    batch = _batch(arrays)
    ref = eng_insert.insert_batch(CFG, init_state(CFG), batch, path="scan")
    spec = skt.make_spec("lsketch", n_shards=4, config=CFG)
    state = skt.create(spec)
    n = len(arrays[0])
    for a in range(0, n, 256):
        chunk = jax.tree.map(lambda x: x[a:a + 256], batch)
        state = skt.ingest(spec, state, chunk)
    assert bool(skt.shards_compatible(spec, state))
    assert _states_equal(ref, skt.merge_all(spec, state))


def test_sharded_queries_match_single_sketch():
    arrays = _overflow_stream(CFG, seed=5)
    src, dst, la, lb, le, w, t = arrays
    batch = _batch(arrays)
    ref = eng_insert.insert_batch(CFG, init_state(CFG), batch, path="scan")
    spec1 = skt.make_spec("lsketch", n_shards=1, config=CFG)
    h1 = skt.ShardedState(shards=jax.tree.map(lambda x: x[None], ref))
    spec4 = skt.make_spec("lsketch", n_shards=4, config=CFG)
    h4 = skt.ingest(spec4, skt.create(spec4), batch)

    q = skt.QueryBatch.edges(src[:64], la[:64], dst[:64], lb[:64])
    assert np.array_equal(skt.query(spec4, h4, q), skt.query(spec1, h1, q))
    q = skt.QueryBatch.edges(src[:64], la[:64], dst[:64], lb[:64],
                             edge_label=le[:64], last=2)
    assert np.array_equal(skt.query(spec4, h4, q), skt.query(spec1, h1, q))
    vq = skt.QueryBatch.vertices(src[:32], la[:32], direction="in")
    assert np.array_equal(skt.query(spec4, h4, vq), skt.query(spec1, h1, vq))
    lq = skt.QueryBatch.labels(np.arange(3, dtype=np.int32))
    assert np.array_equal(skt.query(spec4, h4, lq), skt.query(spec1, h1, lq))


def test_lagging_shard_does_not_leak_expired_windows():
    """A shard that stops receiving traffic must not contribute counters
    the combined stream already expired (global cur_widx reconciliation)."""
    cfg = CFG.replace(pool_capacity=512)
    ws = cfg.subwindow_size
    srcs = _disjoint_row_srcs(cfg, 6)
    spec = skt.make_spec("lsketch", n_shards=4, config=cfg)
    sid = {v: int(skt.shard_assignment(spec, [v], [l])[0]) for v, l in srcs}
    # two sources on different shards
    (va, la_), (vb, lb_) = next(
        ((a, b) for a in srcs for b in srcs if sid[a[0]] != sid[b[0]]))
    state = skt.create(spec)
    early = EdgeBatch(*[jnp.asarray(x, jnp.int32) for x in (
        [va], [100], [la_], [100 % 3], [0], [7], [0])])
    state = skt.ingest(spec, state, early)
    # stream advances far beyond the window on the *other* shard only
    late = EdgeBatch(*[jnp.asarray(x, jnp.int32) for x in (
        [vb], [101], [lb_], [101 % 3], [0], [5], [ws * 50])])
    state = skt.ingest(spec, state, late)
    q = skt.QueryBatch.edges([va], [la_], [100], [100 % 3])
    assert int(skt.query(spec, state, q)[0]) == 0  # expired, not 7
    merged = skt.merge_all(spec, state)
    single = eng_insert.insert_batch(
        cfg, eng_insert.insert_batch(cfg, init_state(cfg), early,
                                     path="scan"), late, path="scan")
    assert _states_equal(merged, single)


# --------------------------------------------------------------------------
# LGS / GSS kinds through the same handle layer
# --------------------------------------------------------------------------

def test_lgs_shard_equivalence_and_queries():
    arrays = random_stream(np.random.default_rng(6), n=400, tmax=2000)
    src, dst, la, lb, le, w, t = arrays
    ref = LGS(d=32, copies=3, c=4, k=4, window_size=400).insert(
        src, dst, la, lb, le, w, t)
    spec = skt.make_spec("lgs", n_shards=4, d=32, copies=3, c=4, k=4,
                         window_size=400)
    state = skt.ingest(spec, skt.create(spec), _batch(arrays))
    assert bool(skt.shards_compatible(spec, state))  # LGS: always
    assert _states_equal(ref.state, skt.merge_all(spec, state))
    # count-min estimates: sharded sum >= truth and == single on answers
    h1 = skt.ShardedState(
        shards=jax.tree.map(lambda x: x[None], ref.state))
    spec1 = spec.replace(n_shards=1)
    q = skt.QueryBatch.edges(src[:40], la[:40], dst[:40], lb[:40])
    out4, out1 = skt.query(spec, state, q), skt.query(spec1, h1, q)
    assert np.array_equal(out4, out1)
    with pytest.raises(NotImplementedError):
        skt.query(spec, state, skt.QueryBatch.labels(np.arange(2)))


def test_gss_kind_matches_object():
    # d=256 keeps the 200-edge stream collision-free across shards (seed
    # chosen so shards_compatible holds, asserted below)
    arrays = random_stream(np.random.default_rng(0), n=200)
    src, dst, la, lb, le, w, t = arrays
    from repro.core import GSS
    g = GSS(d=256).insert(src, dst, weight=w)
    spec = skt.make_spec("gss", n_shards=2, d=256)
    state = skt.ingest(spec, skt.create(spec), _batch(arrays))
    assert bool(skt.shards_compatible(spec, state))
    # labels/time in the query are ignored (degenerate normalization)
    q = skt.QueryBatch.edges(src[:32], la[:32], dst[:32], lb[:32], last=1)
    out = skt.query(spec, state, q)
    for i in range(0, 32, 5):
        assert int(out[i]) == g.edge_weight(int(src[i]), 0, int(dst[i]), 0)


# --------------------------------------------------------------------------
# spec: hashable, validated, JSON round-trip
# --------------------------------------------------------------------------

def test_spec_static_identity():
    a = skt.make_spec("lsketch", n_shards=4, config=CFG)
    b = skt.make_spec("lsketch", n_shards=4, config=CFG)
    assert a == b and hash(a) == hash(b) and len({a, b}) == 1
    assert a != a.replace(n_shards=2)
    assert a.compatible(a.replace(n_shards=2))
    assert not a.compatible(skt.make_spec("gss", config=CFG))
    g = skt.make_spec("lgs", n_shards=2, d=32, copies=2)
    assert g == skt.make_spec("lgs", n_shards=2, d=32, copies=2)
    for spec in (a, g):
        rt = skt.SketchSpec.from_json(spec.to_json())
        assert rt == spec and hash(rt) == hash(spec)  # same jit-cache key
    with pytest.raises(ValueError):
        skt.make_spec("tcm", config=CFG)
    with pytest.raises(ValueError):
        skt.make_spec("lsketch", n_shards=0, config=CFG)
    with pytest.raises(TypeError):
        skt.SketchSpec(kind="lgs", config=CFG)


def test_shard_assignment_is_deterministic_and_balanced():
    spec = skt.make_spec("lsketch", n_shards=8, config=CFG)
    v = np.arange(4096, dtype=np.int32)
    s1 = skt.shard_assignment(spec, v, v % 3)
    s2 = skt.shard_assignment(spec, v, v % 3)
    assert np.array_equal(s1, s2)
    # the host-side hash twin must stay bit-identical to the jnp family
    from repro.core import hashing as hsh
    from repro.sketch.spec import _hash31_np
    x = np.arange(0, 2**16, 7, dtype=np.uint32)
    assert np.array_equal(_hash31_np(x, 1234), np.asarray(hsh.hash31(x, 1234)))
    counts = np.bincount(s1, minlength=8)
    assert counts.min() > 0.5 * counts.mean()  # rough balance
    # different seed -> different partition
    other = skt.make_spec("lsketch", n_shards=8,
                          config=CFG.replace(seed=999))
    assert not np.array_equal(s1, skt.shard_assignment(other, v, v % 3))


# --------------------------------------------------------------------------
# checkpoint round-trip (incl. resharding restore)
# --------------------------------------------------------------------------

def test_checkpoint_roundtrip_same_and_different_shard_count(tmp_path):
    """Same-count restore is bit-exact; a different count triggers the
    key-space ``reshard`` (balanced re-partition): vertex aggregates are
    conserved exactly, occupancy spreads over every target shard instead
    of piling into shard 0, and the handle keeps ingesting correctly.
    (Deeper reshard pins — one-sidedness vs an exact oracle, round-trips,
    pool overflow — live in tests/test_reshard.py.)"""
    arrays = _overflow_stream(CFG, seed=8, n_hot=200, n_cold=600)
    src, dst, la, lb, le, w, t = arrays
    spec4 = skt.make_spec("lsketch", n_shards=4, config=CFG)
    state = skt.ingest(spec4, skt.create(spec4), _batch(arrays))
    skt.save(spec4, state, tmp_path, step=7)
    assert skt.saved_spec(tmp_path) == spec4

    same = skt.restore(spec4, tmp_path)
    assert _states_equal(state, same)

    qv = skt.QueryBatch.vertices(src[:64], la[:64])
    base_v = skt.query(spec4, state, qv)
    for m in (2, 6):  # shrink and grow
        specm = spec4.replace(n_shards=m)
        resharded = skt.restore(specm, tmp_path)
        assert resharded.n_shards == m
        assert np.array_equal(skt.query(specm, resharded, qv), base_v)
        occ = np.asarray(jnp.sum(resharded.shards.key != EMPTY,
                                 axis=(1, 2, 3)))
        # this stream has only 8 distinct source entities (by design), so
        # full balance is not expectable at m=6 — pin no-pileup instead
        # (fine-grained balance is pinned in tests/test_reshard.py)
        assert np.count_nonzero(occ) >= min(m, 4), f"pileup at {m}: {occ}"
        assert occ.max() < occ.sum(), f"single-shard pileup at {m}: {occ}"
        # and the resharded handle keeps ingesting correctly (vertex
        # aggregates sum all matching cells, so placement is invisible)
        more = _batch(tuple(x[:128] for x in arrays))
        rm = skt.ingest(specm, resharded, more)
        s4 = skt.ingest(spec4, skt.restore(spec4, tmp_path), more)
        assert np.array_equal(skt.query(specm, rm, qv),
                              skt.query(spec4, s4, qv))

    with pytest.raises(ValueError):
        skt.restore(skt.make_spec("lsketch", config=CFG.replace(seed=1)),
                    tmp_path)


def test_checkpoint_reshard_handles_contended_shards(tmp_path):
    """A cross-shard-contended checkpoint — which the old merge-based
    shrink had to refuse — reshards fine: the per-shard decode never takes
    the lossy key union, so vertex aggregates are conserved exactly in
    both directions."""
    arrays = random_stream(np.random.default_rng(1), n=400)
    cfg = CFG.replace(d=32, s=4)  # small matrix: contention certain
    spec = skt.make_spec("lsketch", n_shards=4, config=cfg)
    state = skt.ingest(spec, skt.create(spec), _batch(arrays))
    assert not bool(skt.shards_compatible(spec, state))
    skt.save(spec, state, tmp_path)
    qv = skt.QueryBatch.vertices(arrays[0][:32], arrays[2][:32])
    base_v = skt.query(spec, state, qv)
    for m in (2, 8):
        resharded = skt.restore(spec.replace(n_shards=m), tmp_path)
        assert np.array_equal(
            skt.query(spec.replace(n_shards=m), resharded, qv), base_v), m


# --------------------------------------------------------------------------
# placement
# --------------------------------------------------------------------------

def test_namedsharding_placement_preserves_results():
    from repro.launch.mesh import make_smoke_mesh
    arrays = random_stream(np.random.default_rng(9), n=256)
    src, dst, la, lb, le, w, t = arrays
    spec = skt.make_spec("lsketch", n_shards=2, config=CFG)
    mesh = make_smoke_mesh()
    placed = skt.place(spec, skt.create(spec), mesh)
    placed = skt.ingest(spec, placed, _batch(arrays))
    plain = skt.ingest(spec, skt.create(spec), _batch(arrays))
    assert _states_equal(placed.shards, plain.shards)
    q = skt.QueryBatch.edges(src[:16], la[:16], dst[:16], lb[:16])
    assert np.array_equal(skt.query(spec, placed, q),
                          skt.query(spec, plain, q))


# --------------------------------------------------------------------------
# query padding: EMPTY sentinel regression
# --------------------------------------------------------------------------

def test_query_pad_rows_use_empty_sentinel():
    from repro.sketch.query import pad_all
    padded, = pad_all(5, jnp.arange(5, dtype=jnp.int32))
    assert padded.shape[0] == 32
    assert bool(jnp.all(padded[5:] == EMPTY))  # not vertex id 0


# --------------------------------------------------------------------------
# shard-axis Pallas fast path: bit-identity with the vmapped scan
# --------------------------------------------------------------------------

OVERFLOW_CFG = LSketchConfig(d=8, n_blocks=2, F=256, r=2, s=2, c=4, k=4,
                             window_size=400, pool_capacity=8, pool_probes=2)


def _parity_case(cfg, arrays, n_shards):
    """Ingest one stream through both stacked insert paths; assert the
    final handles are bit-identical (state-for-state, incl. pool)."""
    batch = _batch(arrays)
    spec = skt.make_spec("lsketch", n_shards=n_shards, config=cfg)
    scan = skt.ingest(spec, skt.create(spec), batch, path="scan")
    pal = skt.ingest(spec, skt.create(spec), batch, path="pallas")
    assert _states_equal(scan.shards, pal.shards)
    return pal


# one batch's time span per case: CFG's subwindows are 100 units, k = 4;
# "two" and "k-1" re-claim no slot (the walk), "multi" wraps the ring
SPAN_TMAX = {"multi": 2500, "two": 200, "k-1": 300}


def _span_arrays(span):
    rng = np.random.default_rng(20)
    n = 400
    src = rng.integers(0, 300, n).astype(np.int32)
    dst = rng.integers(0, 300, n).astype(np.int32)
    t = (np.full(n, 7, np.int32) if span == "single"
         else np.sort(rng.integers(0, SPAN_TMAX[span], n)).astype(np.int32))
    return (src, dst, src % 3, dst % 3,
            rng.integers(0, 5, n).astype(np.int32),
            rng.integers(1, 4, n).astype(np.int32), t)


@pytest.mark.parametrize("n_shards", [1, 4])
@pytest.mark.parametrize("span", ["single", "multi", "two", "k-1"])
def test_sharded_pallas_matches_scan(n_shards, span):
    """The shard-axis kernel path (one launch for any batch that re-claims
    no ring slot, each edge at its own slot, + in-dispatch scan fallback)
    is bit-identical to the vmapped fused scan on the same partition —
    incl. ring wraparound and pool machinery."""
    _parity_case(CFG, _span_arrays(span), n_shards)


def _boundary_run(case):
    """``(cfg, [arrays per batch])`` of a time-ordered run whose batches
    span subwindow boundaries. "wraps": consecutive 250-unit batches (three
    subwindows each) over five turns of the ring; "overflow": a
    two-subwindow batch that saturates OVERFLOW_CFG's pool; "reclaim":
    subwindows 0 and 4 share ring slot 0, so the later re-claims it inside
    the batch."""
    if case in SPAN_TMAX:
        return CFG, [_span_arrays(case)]
    rng = np.random.default_rng({"wraps": 25, "overflow": 26,
                                 "reclaim": 27}[case])
    if case == "wraps":
        runs = [(250 * j, 250 * (j + 1), 200) for j in range(8)]
    elif case == "overflow":
        runs = [(50, 150, 500)]
    else:
        runs = [(0, 500, 400)]
    n_vertices = 40 if case == "overflow" else 300
    out = []
    for lo, hi, n in runs:
        src = rng.integers(0, n_vertices, n).astype(np.int32)
        dst = rng.integers(0, n_vertices, n).astype(np.int32)
        t = np.sort(rng.integers(lo, hi, n)).astype(np.int32)
        t[0], t[-1] = lo, hi - 1
        out.append((src, dst, src % 3, dst % 3,
                    rng.integers(0, 4, n).astype(np.int32),
                    rng.integers(1, 4, n).astype(np.int32), t))
    return (OVERFLOW_CFG if case == "overflow" else CFG), out


@pytest.mark.parametrize("n_shards", [1, 4])
@pytest.mark.parametrize("case", ["wraps", "overflow", "reclaim"])
def test_stacked_boundary_batches_pallas_matches_scan(case, n_shards):
    """Boundary-spanning batches through the stacked pallas path equal the
    scan bit for bit (state and pool) after every batch: runs of them
    across ring wraparounds, pool overflow landing at each edge's own
    slot, and the re-claim fallback."""
    cfg, runs = _boundary_run(case)
    spec = skt.make_spec("lsketch", n_shards=n_shards, config=cfg)
    scan, pal = skt.create(spec), skt.create(spec)
    for arrays in runs:
        scan = skt.ingest(spec, scan, _batch(arrays), path="scan")
        pal = skt.ingest(spec, pal, _batch(arrays), path="pallas")
        assert _states_equal(scan.shards, pal.shards)
    if case == "overflow":
        assert int(jnp.sum(pal.shards.pool_lost)) > 0, \
            "stream must saturate the pool"
        pool_slots = jnp.any(pal.shards.pool_C != 0, axis=(0, 1))
        assert bool(pool_slots[0]) and bool(pool_slots[1]), \
            "pool weight must land at both subwindows' slots"


WALKS = {"two": True, "k-1": True, "wraps": True, "overflow": True,
         "reclaim": False}


@pytest.mark.parametrize("n_shards", [1, 4])
@pytest.mark.parametrize("case", list(WALKS))
def test_walk_rule_and_scan_batches_counter(case, n_shards):
    """``walk_eligible`` takes the walk for the boundary cases that
    re-claim no slot and the scan for an in-batch re-claim, batch by batch
    against the ring as ingest leaves it; the host's
    ``ingest.scan_batches`` counter agrees with it."""
    from repro.sketch import trace
    from repro.sketch.ingest import _dispatch_stacked, _partition_stack

    cfg, runs = _boundary_run(case)
    spec = skt.make_spec("lsketch", n_shards=n_shards, config=cfg)
    state = skt.create(spec)
    plan = jax.jit(jax.vmap(functools.partial(eng_insert.ring_plan, cfg)))
    trace.reset()
    trace.enable()
    try:
        n_scan = 0
        for arrays in runs:
            stacked, n_valid = _partition_stack(spec, _batch(arrays))
            sh = state.shards
            valid, _, p = plan(sh.slot_widx, sh.cur_widx, stacked.time,
                               n_valid)
            walks = bool(eng_insert.walk_eligible(p, valid))
            assert walks == WALKS[case]
            n_scan += not walks
            state = _dispatch_stacked(spec, state, stacked, n_valid, "auto")
        counts = trace.snapshot()["counts"]
    finally:
        trace.disable()
        trace.reset()
    assert counts["ingest.scan_batches"] == n_scan
    assert counts["ingest.boundary_batches"] == len(runs)


@pytest.mark.parametrize("case", ["two", "reclaim"])
def test_fused_pallas_matches_scan_across_boundary(case):
    """The single-shard fused entry follows the same walk rule: a
    two-subwindow batch takes the walk with per-edge slots, a re-claim
    the scan, and both equal the scan and the chunked reference."""
    cfg, (arrays,) = _boundary_run(case)
    batch = _batch(arrays)
    ref = eng_insert.insert_batch(cfg, init_state(cfg), batch, path="chunked")
    for path in ("scan", "pallas"):
        got = eng_insert.insert_batch(cfg, init_state(cfg), batch, path=path)
        assert _states_equal(ref, got), path


@pytest.mark.parametrize("n_shards", [1, 4])
def test_sharded_pallas_matches_scan_under_pool_overflow(n_shards):
    rng = np.random.default_rng(21)
    n = 500
    src = rng.integers(0, 400, n).astype(np.int32)
    dst = rng.integers(0, 400, n).astype(np.int32)
    arrays = (src, dst, src % 3, dst % 3,
              rng.integers(0, 4, n).astype(np.int32),
              rng.integers(1, 4, n).astype(np.int32),
              np.full(n, 3, np.int32))
    pal = _parity_case(OVERFLOW_CFG, arrays, n_shards)
    assert int(jnp.sum(pal.shards.pool_lost)) > 0, \
        "stream must saturate the pool"


def test_sharded_pallas_empty_shard_rows_are_noops():
    """All edges share one source entity -> every other shard's row is
    pure replicate-last padding with n_valid == 0; the kernel path must
    treat those rows as strict no-ops (bit-identical to scan, and the
    untouched shards stay exactly at their initial state)."""
    n = 300
    rng = np.random.default_rng(22)
    arrays = (np.full(n, 5, np.int32), rng.integers(0, 300, n),
              np.full(n, 2, np.int32), rng.integers(0, 3, n),
              rng.integers(0, 5, n), rng.integers(1, 4, n),
              np.full(n, 7, np.int32))
    arrays = tuple(np.asarray(a, np.int32) for a in arrays)
    pal = _parity_case(CFG, arrays, 4)
    spec = skt.make_spec("lsketch", n_shards=4, config=CFG)
    sid = int(skt.shard_assignment(spec, [5], [2])[0])
    fresh = skt.create(spec)
    for s in range(4):
        if s == sid:
            continue
        assert _states_equal(skt.unstack_state(pal, s),
                             skt.unstack_state(fresh, s))


def test_sharded_pallas_scan_parity_property():
    """Hypothesis sweep of the bit-identity: random streams (time-ordered,
    arbitrary subwindow spans, repeated edges), random shard counts —
    kernel path == scan path, always. Includes the replicate-last padding
    and the n_valid=0 empty-shard row by construction (tiny vertex pools
    leave shards empty under the endpoint hash)."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 200),
           n_vertices=st.sampled_from([2, 10, 200]),
           tmax=st.sampled_from([1, 300, 3000]),
           n_shards=st.sampled_from([1, 2, 4, 5]))
    def check(seed, n, n_vertices, tmax, n_shards):
        rng = np.random.default_rng(seed)
        src = rng.integers(0, n_vertices, n).astype(np.int32)
        dst = rng.integers(0, n_vertices, n).astype(np.int32)
        arrays = (src, dst, src % 3, dst % 3,
                  rng.integers(0, 5, n).astype(np.int32),
                  rng.integers(1, 4, n).astype(np.int32),
                  np.sort(rng.integers(0, tmax, n)).astype(np.int32))
        _parity_case(CFG, arrays, n_shards)

    check()


def _walk_inputs(seed, S, B, n_vertices, w_lo):
    """Addressed probes, label buckets and weights for a [S, B] stack."""
    from repro.core import hashing as hsh
    from repro.core.lsketch import edge_probes

    rng = np.random.default_rng(seed)
    src = jnp.asarray(rng.integers(0, n_vertices, (S, B)), jnp.int32)
    dst = jnp.asarray(rng.integers(0, n_vertices, (S, B)), jnp.int32)
    le = jnp.asarray(rng.integers(0, 5, (S, B)), jnp.int32)
    w = jnp.asarray(rng.integers(w_lo, 3, (S, B)), jnp.int32)
    probes = edge_probes(CFG, precompute(CFG, src, src % 3),
                         precompute(CFG, dst, dst % 3))
    return probes, hsh.edge_label_bucket(le, CFG.c, CFG.seed), w


def test_pallas_kernel_bit_identical_to_xla_twin():
    """The Pallas key-walk kernel (interpret mode) and its XLA lowering
    (``sketch_insert_stream_walk``) return the same keys and landing
    cells on identical inputs — the anchor that ties the TPU program to
    the compiled path the CPU runs. Bins past one SMEM chunk, zero
    weights and a half-full starting key plane are all covered."""
    from repro.kernels.sketch_insert.kernel import CHUNK
    from repro.kernels.sketch_insert.ops import (_key_walk_pallas,
                                                 _key_walk_xla)

    S, B = 2, 640  # ~160 edges per bin: every bin spans two chunks
    probes, _, w = _walk_inputs(23, S, B, 300, 0)  # weights incl. zeros
    warm, _, w0 = _walk_inputs(5, S, 256, 300, 1)
    key0 = jnp.full((S, 2, CFG.d, CFG.d), EMPTY, jnp.int32)
    key_t, _, _ = _key_walk_xla(CFG, warm, w0, key0, 256)
    assert B // CFG.n_blocks ** 2 > CHUNK
    ker = _key_walk_pallas(CFG, probes, w, key_t, B, interpret=True)
    xla = _key_walk_xla(CFG, probes, w, key_t, B)
    for a, b in zip(ker, xla):
        assert bool(jnp.array_equal(a, b))
    assert int(jnp.sum(ker[1] > 0)) > 0


def test_small_max_bin_drops_overflow_to_pool_on_both_lowerings():
    """``max_bin`` is a tuning knob: a bin's overflow edges are marked
    not-inserted and fall to the additional pool. The XLA stream walk
    must reproduce the kernel's truncated-bin semantics — regression for
    a divergence where the walk ignored ``max_bin`` and inserted
    overflow into the matrix instead."""
    from repro.kernels.sketch_insert.ops import (
        _key_walk_pallas, _key_walk_xla, matrix_insert_binned_sharded)

    S, B, MB = 2, 96, 4  # MB far below the per-bin fill
    probes, lei, w = _walk_inputs(24, S, B, 50, 1)
    key0 = jnp.full((S, 2, CFG.d, CFG.d), EMPTY, jnp.int32)
    ker = _key_walk_pallas(CFG, probes, w, key0, MB, interpret=True)
    xla = _key_walk_xla(CFG, probes, w, key0, MB)
    for a, b in zip(ker, xla):
        assert bool(jnp.array_equal(a, b))
    assert int(jnp.sum(xla[1] > 0)) <= S * CFG.n_blocks ** 2 * MB
    base = jax.tree.map(lambda x: jnp.stack([x] * S), init_state(CFG))
    out = jax.jit(lambda st: matrix_insert_binned_sharded(
        CFG, st, probes, lei, w, jnp.zeros((S, B), jnp.int32),
        max_bin=MB))(base)
    # the cap must actually bite: some edges landed in the pool
    assert int(jnp.sum(out.pool_key[..., 0] != EMPTY)) > 0


# --------------------------------------------------------------------------
# AsyncIngestor: pipelined == synchronous, flush contract
# --------------------------------------------------------------------------

def test_async_ingestor_matches_sync_with_interleaved_queries():
    """Double-buffered pipelined ingest of a chunked stream — with queries
    interleaved between submissions — ends bit-identical to eager
    synchronous ingest of the same chunks (flush semantics: every query
    sees every batch submitted before it; no reordering across subwindow
    boundaries)."""
    arrays = _overflow_stream(CFG, seed=30, n_hot=300, n_cold=900)
    batch = _batch(arrays)
    spec = skt.make_spec("lsketch", n_shards=4, config=CFG)
    ing = skt.AsyncIngestor(spec)
    sync = skt.create(spec)
    n = len(arrays[0])
    q = skt.QueryBatch.edges(arrays[0][:16], arrays[2][:16],
                             arrays[1][:16], arrays[3][:16])
    for i, a in enumerate(range(0, n, 256)):
        chunk = jax.tree.map(lambda x: x[a:a + 256], batch)
        ing.submit(chunk)
        sync = skt.ingest(spec, sync, chunk)
        if i % 2 == 1:  # interleaved query: must flush, must agree
            assert np.array_equal(skt.query(spec, ing.state, q),
                                  skt.query(spec, sync, q))
            assert ing.pending == 0  # reading .state flushed the pipe
    assert _states_equal(ing.flush().shards, sync.shards)


def test_async_ingestor_flush_contract():
    spec = skt.make_spec("lsketch", n_shards=2, config=CFG)
    ing = skt.AsyncIngestor(spec)
    st0 = ing.flush()
    assert ing.flush() is st0  # idempotent, no staged work
    ing.submit(jax.tree.map(lambda x: x[:0], _batch(
        _overflow_stream(CFG, seed=31))))  # empty batch: no-op
    assert ing.pending == 0 and ing.flush() is st0
    arrays = tuple(x[:64] for x in _overflow_stream(CFG, seed=31))
    ing.submit(_batch(arrays))
    assert ing.pending == 1  # staged, not yet dispatched
    st1 = ing.flush()
    assert ing.pending == 0 and st1 is not st0
    assert ing.flush() is st1
    # pipelined AsyncIngestor == one-shot ingest of the same batch
    ref = skt.ingest(spec, skt.create(spec), _batch(arrays))
    assert _states_equal(st1.shards, ref.shards)


# --------------------------------------------------------------------------
# stacked-ingest jit: compiled (non-interpreted) scan + compile count
# --------------------------------------------------------------------------

@pytest.mark.parametrize("path", ["scan", "pallas"])
def test_stacked_ingest_single_trace_across_batches(path):
    """Compile-count regression for the stacked ingest jit: one trace per
    (spec, bucketed shape, path), zero further traces however many
    subwindow boundaries (or empty shards) later batches contain."""
    spec = skt.make_spec("lsketch", n_shards=4,
                         config=CFG.replace(seed=4242))  # fresh jit keys
    rng = np.random.default_rng(33)

    def some_batch(tmax):
        n = 160  # per-shard counts stay inside one 64-bucket
        src = rng.integers(0, 300, n).astype(np.int32)
        dst = rng.integers(0, 300, n).astype(np.int32)
        return _batch((src, dst, src % 3, dst % 3,
                       rng.integers(0, 5, n).astype(np.int32),
                       np.ones(n, np.int32),
                       np.sort(rng.integers(0, tmax, n)).astype(np.int32)))

    state = skt.create(spec)
    before = eng_insert.TRACE_COUNTS["stacked"]
    state = skt.ingest(spec, state, some_batch(50), path=path)
    assert eng_insert.TRACE_COUNTS["stacked"] - before == 1
    state = skt.ingest(spec, state, some_batch(200), path=path)
    state = skt.ingest(spec, state, some_batch(3000), path=path)
    assert eng_insert.TRACE_COUNTS["stacked"] - before == 1, \
        "extra subwindows must not add traces or dispatches"


def test_query_padding_does_not_change_answers():
    """Answers at every batch size (hence padding amount) match the scalar
    path — pad rows can't alias real probes whatever fills them."""
    from repro.core import LSketch
    arrays = random_stream(np.random.default_rng(10), n=200)
    src, dst, la, lb, le, w, t = arrays
    sk = LSketch(CFG).insert(src, dst, la, lb, le, w, t)
    spec1 = skt.make_spec("lsketch", n_shards=1, config=CFG)
    h = skt.ShardedState(shards=jax.tree.map(lambda x: x[None], sk.state))
    for nq in (1, 5, 31, 33):
        q = skt.QueryBatch.edges(src[:nq], la[:nq], dst[:nq], lb[:nq])
        out = skt.query(spec1, h, q)
        assert out.shape == (nq,)
        for i in range(nq):
            assert int(out[i]) == sk.edge_weight(
                int(src[i]), int(la[i]), int(dst[i]), int(lb[i]))
