"""In-program spans and counters (``repro.sketch.trace``) on the served
ingest path, and the named scopes of the stacked ingest program."""

import os
import sys
import threading

import numpy as np
import pytest
import jax.numpy as jnp

from repro import sketch as skt
from repro.core import LSketchConfig
from repro.core.types import EdgeBatch
from repro.launch.serve_sketch import SketchServer
from repro.sketch import trace
from repro.sketch.ingest import _FIELDS, _ingest_stacked_lsketch, \
    _partition_stack

# four ring slots of ten time units each
CFG = LSketchConfig(d=32, n_blocks=2, F=1024, r=4, s=4, c=4, k=4,
                    window_size=40, pool_capacity=64, pool_probes=4)


@pytest.fixture
def on():
    trace.reset()
    trace.enable()
    yield
    trace.disable()
    trace.reset()


def _batch(rng, times, src=None) -> EdgeBatch:
    n = len(times)
    src = rng.integers(0, 500, n) if src is None else np.asarray(src)
    dst = rng.integers(0, 500, n)
    cols = {"src": src, "dst": dst, "src_label": src % 3,
            "dst_label": dst % 3, "edge_label": rng.integers(0, 5, n),
            "weight": np.ones(n), "time": np.asarray(times)}
    return EdgeBatch(**{k: jnp.asarray(v, jnp.int32)
                        for k, v in cols.items()})


def _parent(spans, i):
    p = spans[i][3]
    return spans[p][0] if p >= 0 else None


def test_off_records_nothing():
    trace.disable()
    trace.reset()
    a, b = trace.span("server.ingest"), trace.span("ingest.partition")
    assert a is b  # the one shared no-op context
    with a:
        trace.count("ingest.rows", 7)
    spec = skt.SketchSpec(kind="lsketch", config=CFG, n_shards=2)
    server = SketchServer(spec, query_path="pallas")
    server.ingest(_batch(np.random.default_rng(0), np.arange(64) // 8))
    server.flush()
    snap = trace.snapshot()
    assert snap["spans"] == [] and snap["counts"] == {}
    assert set(snap["planes"]) == {"build", "delta", "evict"}


def test_served_ingest_spans(on):
    rng = np.random.default_rng(1)
    spec = skt.SketchSpec(kind="lsketch", config=CFG, n_shards=1)
    server = SketchServer(spec, query_path="pallas")
    server.ingest(_batch(rng, np.full(96, 3)))
    server.ingest(_batch(rng, np.full(96, 4)))
    spans = trace.snapshot()["spans"]
    names = [s[0] for s in spans]
    assert names.count("server.ingest") == 2
    assert all(s[2] is not None and s[2] >= s[1] for s in spans)
    for i, n in enumerate(names):
        if n in ("ingest.dispatch", "ingest.partition", "planes.prewarm"):
            assert _parent(spans, i) == "server.ingest", n
        if n in ("planes.wait", "planes.build", "planes.fold"):
            assert _parent(spans, i) == "planes.prewarm", n
    # the second call dispatches the first batch, then waits on its delta
    assert names.count("ingest.partition") == 2
    assert names.count("ingest.dispatch") == 1
    assert "planes.wait" in names

    # a flush drains the staged batch and prewarms outside any ingest call
    trace.reset()
    server.submit("edge", src=1, la=1, dst=2, lb=2)
    assert server.flush() == 1
    spans = trace.snapshot()["spans"]
    roots = {s[0] for s in spans if s[3] < 0}
    assert roots == {"ingest.dispatch", "planes.prewarm"}


def test_boundary_batches_match_a_hand_count(on):
    rng = np.random.default_rng(2)
    spec = skt.SketchSpec(kind="lsketch", config=CFG, n_shards=1)
    # (first time, last time) of each batch; subwindows are ten units
    runs = [(0, 9), (5, 15), (20, 29), (29, 41), (41, 42), (48, 70)]
    crosses = [False, True, False, True, False, True]
    for lo, hi in runs:
        times = np.sort(rng.integers(lo, hi + 1, 80))
        times[0], times[-1] = lo, hi
        _partition_stack(spec, _batch(rng, times))
    counts = trace.snapshot()["counts"]
    assert counts["ingest.boundary_batches"] == sum(crosses)
    assert counts["ingest.batches"] == len(runs)
    assert counts["ingest.rows"] == 80 * len(runs)


def test_boundary_is_per_shard_and_pad_rows_at_two_shards(on):
    """A batch that spans a boundary only across shards (each shard's rows
    in one subwindow) takes the key walk, so it is no boundary batch; the
    pad rows are the stacked rows that ``n_valid`` masks."""
    rng = np.random.default_rng(3)
    spec = skt.SketchSpec(kind="lsketch", config=CFG, n_shards=2)
    cand = np.arange(2000)
    sid = skt.shard_assignment(spec, cand, cand % 3)
    zero, one = cand[sid == 0][:70], cand[sid == 1][:50]
    src = np.concatenate([zero, one])
    times = np.concatenate([np.full(70, 9), np.full(50, 10)])
    stacked, n_valid = _partition_stack(spec, _batch(rng, times, src))
    counts = trace.snapshot()["counts"]
    assert counts["ingest.boundary_batches"] == 0
    assert np.asarray(n_valid).tolist() == [70, 50]
    assert counts["ingest.pad_rows"] == stacked.src.size - 120
    assert counts["ingest.pad_rows"] == 2 * 96 - 120  # rows padded to 96

    times = np.concatenate([np.full(69, 9), [10], np.full(50, 10)])
    _partition_stack(spec, _batch(rng, times, src))
    assert trace.snapshot()["counts"]["ingest.boundary_batches"] == 1


def test_spans_nest_per_thread_and_counts_add_up(on):
    """Threads share the record: each span's parent is the span its own
    thread had open, and no count is lost."""
    n_threads, n_iter = 2 * (os.cpu_count() or 1) + 2, 200
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def work(k):
            for _ in range(n_iter):
                with trace.span(f"outer{k}"):
                    with trace.span(f"inner{k}"):
                        trace.count("n")
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    snap = trace.snapshot()
    assert snap["counts"]["n"] == n_threads * n_iter
    spans = snap["spans"]
    assert len(spans) == 2 * n_threads * n_iter
    for i, (name, _, _, _) in enumerate(spans):
        if name.startswith("inner"):
            assert _parent(spans, i) == "outer" + name[len("inner"):]
        else:
            assert _parent(spans, i) is None


def test_ingest_program_carries_its_scopes():
    """The scan program's instructions carry the ``ingest.*`` scopes in
    their ``op_name`` metadata (the key walk's is checked where the walk
    compiles, in test_tpu_compile.py)."""
    spec = skt.SketchSpec(kind="lsketch", config=CFG, n_shards=1)
    batch = EdgeBatch(**{f: jnp.zeros((1, 64), jnp.int32) for f in _FIELDS})
    text = _ingest_stacked_lsketch.lower(
        CFG, None, skt.create(spec).shards, batch,
        jnp.zeros((1,), jnp.int32), use_pallas=False,
        emit_delta=True).compile().as_text()
    for scope in ("plan", "reclaim", "scan", "delta"):
        assert f"/ingest.{scope}/" in text, scope
    assert "ingest.walk" not in text
