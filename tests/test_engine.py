"""Engine-layer contracts: fused insert equivalence + single-dispatch.

The properties the engine layer must uphold (ISSUE 1 acceptance):

  * the fused multi-subwindow scan insert and the Pallas binned path are
    bit-identical to the sequential per-subwindow reference across
    subwindow boundaries, ring wraparound, and pool overflow;
  * query answers match the paper-literal prime-product oracle;
  * one jit dispatch (and one trace) per ``insert_batch`` call regardless
    of how many subwindows the batch spans;
  * batched queries take arrays end-to-end on LSketch, LGS, and GSS, and
    agree with the scalar paths.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from conftest import random_stream
from repro.core import (GSS, LGS, LSketch, LSketchConfig, EdgeBatch,
                        init_state)
from repro.core.ref_prime import PrimeLSketch
from repro.engine import WindowRing
from repro.engine import insert as eng_insert
from repro.engine import query_batch as qb

CFG = LSketchConfig(d=64, n_blocks=4, F=512, r=4, s=4, c=4, k=4,
                    window_size=400, pool_capacity=512, pool_probes=16)


def _batch(arrays) -> EdgeBatch:
    return EdgeBatch(*[jnp.asarray(x, jnp.int32) for x in arrays])


def _states_equal(a, b) -> bool:
    return all(bool(jnp.array_equal(x, y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _stream(seed, n=300, tmax=800, **kw):
    return random_stream(np.random.default_rng(seed), n=n, tmax=tmax, **kw)


# --------------------------------------------------------------------------
# bit-identical state: fused scan + Pallas binned vs sequential reference
# --------------------------------------------------------------------------

@pytest.mark.parametrize("seed,tmax,label", [
    (0, 300, "few boundaries"),
    (1, 2500, "ring wraparound (many subwindows expire mid-stream)"),
    (2, 799, "exactly one full window"),
])
def test_fused_scan_matches_chunked_reference(seed, tmax, label):
    arrays = _stream(seed, tmax=tmax)
    batch = _batch(arrays)
    ref = eng_insert.insert_batch_chunked(CFG, init_state(CFG), batch)
    fused = eng_insert.insert_batch(CFG, init_state(CFG), batch, path="scan")
    assert _states_equal(ref, fused), label


def test_pallas_binned_matches_reference_single_and_multi():
    arrays = _stream(3, tmax=1200)
    batch = _batch(arrays)
    ref = eng_insert.insert_batch_chunked(CFG, init_state(CFG), batch)
    pal = eng_insert.insert_batch(CFG, init_state(CFG), batch, path="pallas")
    assert _states_equal(ref, pal)  # 12 subwindows re-claim: the scan
    one = _batch(arrays[:6] + (np.full(len(arrays[0]), 7, np.int32),))
    ref1 = eng_insert.insert_batch_chunked(CFG, init_state(CFG), one)
    pal1 = eng_insert.insert_batch(CFG, init_state(CFG), one, path="pallas")
    assert _states_equal(ref1, pal1)  # single subwindow: kernel path


def test_fused_matches_reference_under_pool_overflow():
    cfg = CFG.replace(pool_capacity=8, pool_probes=2, d=8, n_blocks=2,
                      F=256, r=2, s=2)
    arrays = _stream(4, n=500, n_vertices=400, tmax=1500)
    batch = _batch(arrays)
    ref = eng_insert.insert_batch_chunked(cfg, init_state(cfg), batch)
    fused = eng_insert.insert_batch(cfg, init_state(cfg), batch, path="scan")
    assert int(ref.pool_lost) > 0, "stream must saturate the pool"
    assert _states_equal(ref, fused)


def test_fused_incremental_batches_compose():
    """Feeding one stream as many fused batches == one fused batch."""
    arrays = _stream(5, n=400, tmax=2000)
    whole = _batch(arrays)
    st_whole = eng_insert.insert_batch(CFG, init_state(CFG), whole,
                                       path="scan")
    st_inc = init_state(CFG)
    for a in range(0, 400, 64):
        chunk = jax.tree.map(lambda x: x[a:a + 64], whole)
        st_inc = eng_insert.insert_batch(CFG, st_inc, chunk, path="scan")
    assert _states_equal(st_whole, st_inc)


def test_fused_queries_match_prime_oracle():
    arrays = _stream(6, n=350, tmax=2200)
    src, dst, la, lb, le, w, t = arrays
    sk = LSketch(CFG, eng_insert.insert_batch(
        CFG, init_state(CFG), _batch(arrays), path="scan"))
    oracle = PrimeLSketch(CFG)
    for i in range(len(src)):
        oracle.insert(int(src[i]), int(dst[i]), int(la[i]), int(lb[i]),
                      int(le[i]), int(w[i]), int(t[i]))
    if oracle.pool_lost or int(sk.state.pool_lost):
        pytest.skip("saturated pool: exactness not guaranteed")
    for i in range(0, len(src), 13):
        for last in (None, 1, 3):
            assert sk.edge_weight(int(src[i]), int(la[i]), int(dst[i]),
                                  int(lb[i]), last=last) == \
                oracle.edge_weight(int(src[i]), int(la[i]), int(dst[i]),
                                   int(lb[i]), last=last)


# --------------------------------------------------------------------------
# single dispatch / compile count
# --------------------------------------------------------------------------

def test_one_trace_regardless_of_subwindow_span():
    """The acceptance criterion: batches spanning 1, 2, and many subwindows
    hit the same compiled executable — zero extra traces, one dispatch."""
    cfg = CFG
    n = 256  # == its own size bucket, so every batch shares one shape
    rng = np.random.default_rng(7)

    def batch_spanning(tmax):
        s, d, la, lb, le, w, _ = _stream(8, n=n)
        t = np.sort(rng.integers(0, tmax, n)).astype(np.int32)
        return _batch((s, d, la, lb, le, w, t))

    state = init_state(cfg)
    before = eng_insert.TRACE_COUNTS["fused"]
    state = eng_insert.insert_batch(cfg, state, batch_spanning(50),
                                    path="scan")      # 1 subwindow
    traces_first = eng_insert.TRACE_COUNTS["fused"] - before
    assert traces_first == 1
    state = eng_insert.insert_batch(cfg, state, batch_spanning(200),
                                    path="scan")      # ~2 subwindows
    state = eng_insert.insert_batch(cfg, state, batch_spanning(3000),
                                    path="scan")      # many + wraparound
    assert eng_insert.TRACE_COUNTS["fused"] - before == 1, \
        "extra subwindows must not add traces or dispatches"


def test_empty_batch_is_noop():
    empty = jax.tree.map(lambda x: x[:0], _batch(_stream(9)))
    st = init_state(CFG)
    assert eng_insert.insert_batch(CFG, st, empty) is st
    sk = LSketch(CFG)
    sk.insert(np.array([], np.int32), np.array([], np.int32))
    lgs = LGS(d=16, copies=2, window_size=100)
    lgs.insert(np.array([], np.int32), np.array([], np.int32))


# --------------------------------------------------------------------------
# WindowRing: LGS routes through the same ring; masks agree
# --------------------------------------------------------------------------

def test_lgs_fused_matches_per_subwindow_replay():
    arrays = _stream(10, n=300, tmax=2000)
    src, dst, la, lb, le, w, t = arrays
    lgs = LGS(d=32, copies=3, c=4, k=4, window_size=400)
    lgs.insert(src, dst, la, lb, le, w, t)
    # replay per subwindow through the same fused entry (one subwindow per
    # call == the legacy chunked behavior)
    ref = LGS(d=32, copies=3, c=4, k=4, window_size=400)
    widx = t // ref.cfg.subwindow_size
    for wv in np.unique(widx):
        m = widx == wv
        ref.insert(src[m], dst[m], la[m], lb[m], le[m], w[m], t[m])
    assert _states_equal(lgs.state, ref.state)


def test_window_ring_mask_matches_legacy_semantics():
    ring = WindowRing(4)
    slot_widx = jnp.asarray([8, 5, 6, 7], jnp.int32)
    cur = jnp.asarray(8, jnp.int32)
    assert ring.valid_mask(slot_widx, cur).tolist() == [True, True, True, True]
    assert ring.valid_mask(slot_widx, cur, last=1).tolist() == \
        [True, False, False, False]
    assert ring.valid_mask(slot_widx, cur, last=2).tolist() == \
        [True, False, False, True]


def test_lgs_reachable_uses_full_window_mask():
    """Regression: the old code had a dead conditional on max_hops; the walk
    must see the whole live window however many hops are allowed."""
    lgs = LGS(d=64, copies=2, c=2, k=4, window_size=400)
    lgs.insert(np.array([1]), np.array([2]), np.array([0]), np.array([0]),
               np.array([0]), np.array([1]), np.array([50]))
    lgs.insert(np.array([2]), np.array([3]), np.array([0]), np.array([0]),
               np.array([0]), np.array([1]), np.array([150]))
    assert lgs.reachable(1, 0, 3, 0, max_hops=8)
    assert lgs.reachable(1, 0, 3, 0, max_hops=1) in (False, True)  # no crash


# --------------------------------------------------------------------------
# batched query frontend: arrays end-to-end, all three sketches
# --------------------------------------------------------------------------

def test_batched_queries_match_scalar_paths_lsketch():
    arrays = _stream(11, n=250)
    src, dst, la, lb, le, w, t = arrays
    sk = LSketch(CFG).insert(src, dst, la, lb, le, w, t)
    q = slice(0, 100)
    batched = qb.edge_weight_batch(sk, src[q], la[q], dst[q], lb[q])
    batched_le = qb.edge_weight_batch(sk, src[q], la[q], dst[q], lb[q],
                                      edge_label=le[q], last=2)
    for i in range(0, 100, 9):
        assert int(batched[i]) == sk.edge_weight(
            int(src[i]), int(la[i]), int(dst[i]), int(lb[i]))
        assert int(batched_le[i]) == sk.edge_weight(
            int(src[i]), int(la[i]), int(dst[i]), int(lb[i]),
            le=int(le[i]), last=2)
    vs = np.arange(20, dtype=np.int32)
    vw = qb.vertex_weight_batch(sk, vs, vs % 3, direction="in")
    for v in range(0, 20, 7):
        assert int(vw[v]) == sk.vertex_weight(v, v % 3, direction="in")
    labs = np.arange(3, dtype=np.int32)
    agg = qb.label_aggregate_batch(sk, labs)
    for l in range(3):
        assert int(agg[l]) == sk.label_aggregate(l)


def test_batched_queries_lgs_and_gss():
    arrays = _stream(12, n=200)
    src, dst, la, lb, le, w, t = arrays
    lgs = LGS(d=32, copies=3, c=4, k=4, window_size=400).insert(
        src, dst, la, lb, le, w, t)
    out = qb.edge_weight_batch(lgs, src[:50], la[:50], dst[:50], lb[:50])
    assert out.shape == (50,)
    for i in range(0, 50, 11):
        assert int(out[i]) == lgs.edge_weight(int(src[i]), int(la[i]),
                                              int(dst[i]), int(lb[i]))
    # array-in -> array-out through the object API too
    arr = lgs.edge_weight(src[:8], la[:8], dst[:8], lb[:8])
    assert isinstance(arr, np.ndarray) and arr.shape == (8,)
    with pytest.raises(NotImplementedError):
        qb.label_aggregate_batch(lgs, np.arange(2))

    g = GSS(d=64).insert(src, dst, weight=w)
    gout = qb.edge_weight_batch(g, src[:40], la[:40], dst[:40], lb[:40])
    for i in range(0, 40, 7):
        assert int(gout[i]) == g.edge_weight(int(src[i]), 0, int(dst[i]), 0)


def test_scalar_object_api_unchanged():
    arrays = _stream(13, n=150)
    src, dst, la, lb, le, w, t = arrays
    sk = LSketch(CFG).insert(src, dst, la, lb, le, w, t)
    out = sk.edge_weight(int(src[0]), int(la[0]), int(dst[0]), int(lb[0]))
    assert isinstance(out, int)
    arr = sk.edge_weight(src[:5], la[:5], dst[:5], lb[:5])
    assert isinstance(arr, np.ndarray) and arr.shape == (5,)
