"""The plain reference: its fast first-fit solve equals the literal
item-by-item replay, and the program (its CPU lowering) equals it."""

import numpy as np
import pytest

from bench.reference import Reference, first_fit, sequential_state
from bench.stream import EdgeStream, StreamParams

PARAMS = StreamParams(n_vertices=3000, n_vertex_labels=4, n_edge_labels=10,
                      zipf_a=1.1, duplicate_rate=0.3,
                      edges_per_time_unit=(120000, 2880))


def cfg(d, q):
    return dict(d=d, n_blocks=2, F=1024, r=8, s=8, c=16, k=8,
                window_size=1440, pool_capacity=q, pool_probes=16,
                seed=1234)


def equal_states(fast: dict, seq: dict) -> dict:
    bad = {k: int(np.sum(fast[k] != seq[k])) for k in
           ("key", "C", "pool_key", "pool_C", "pool_P", "pool_lost",
            "slot_widx")}
    nz = np.flatnonzero(seq["P"])
    bad["P"] = int(not (np.array_equal(nz, fast["P"][0])
                        and np.array_equal(seq["P"][nz], fast["P"][1])))
    return bad


@pytest.mark.parametrize("d,q", [(16, 32), (32, 256), (64, 4096)])
def test_fast_reference_equals_sequential_replay(d, q):
    """Small matrices and pools: collisions, pool use and lost weight all
    occur, and the deferred-acceptance solve still equals the replay."""
    cols = EdgeStream(PARAMS, 7).extend(1).slice(0, 12000)
    ref = Reference(cfg(d, q), cols)
    bad = equal_states(ref.state(), sequential_state(cfg(d, q), cols))
    assert not any(bad.values()), bad
    if d == 16:
        assert ref.state()["pool_lost"][0] > 0  # the regime was exercised


def test_first_fit_is_greedy_in_arrival_order():
    rng = np.random.default_rng(0)
    cand = rng.integers(0, 40, (200, 5))
    pos = first_fit(cand, 40)
    taken = set()
    for i, row in enumerate(cand):
        want = next((j for j, c in enumerate(row) if c not in taken), -1)
        assert pos[i] == want
        if want >= 0:
            taken.add(row[want])


def test_program_equals_reference_on_cpu():
    """The served path (``SketchServer``, CPU lowering) against the
    reference: the whole state and answers of every query kind, at flush
    points spread through a stream whose batches cross subwindows."""
    import jax

    from repro import sketch as skt
    from repro.core import LSketchConfig
    from repro.core.types import EdgeBatch
    from repro.launch.serve_sketch import SketchServer
    from bench.harness import (program_state, reference_answers,
                               state_mismatches)
    from bench.loadgen import request
    c = cfg(64, 256)
    cols = EdgeStream(PARAMS, 11).extend(1).slice(0, 24000)
    spec = skt.SketchSpec(kind="lsketch", config=LSketchConfig(**c),
                          n_shards=1)
    server = SketchServer(spec, max_batch=1 << 20)
    groups = [{"kind": "edge"}, {"kind": "edge", "le": True},
              {"kind": "vertex", "direction": "out"},
              {"kind": "vertex", "direction": "in", "le": True},
              {"kind": "label", "direction": "out"},
              {"kind": "label", "direction": "in", "le": True}]
    rng = np.random.default_rng(1)
    reqs, got, n_at = [], [], []
    B = 1024
    for a in range(0, len(cols["src"]), B):
        server.ingest(EdgeBatch(**{f: v[a:a + B] for f, v in cols.items()}))
        if (a // B) % 5 == 2:
            batch = [request(groups[i % len(groups)], cols,
                             int(rng.integers(0, a + B)))
                     for i in range(36)]
            hs = [server.submit(k, **kw) for k, kw in batch]
            server.flush()
            reqs += batch
            got += [h.answer for h in hs]
            n_at += [a + B] * len(batch)
    ref = Reference(c, cols)
    want = reference_answers(ref, reqs, np.array(n_at))
    assert np.array_equal(np.array(got), want)
    bad = state_mismatches(program_state(jax.device_get(server.state.shards)),
                           ref.state())
    assert not any(bad.values()), bad


def test_lower_precision_control_differs():
    """The control: the same reference with 9-bit fingerprints in place of
    the configuration's 10 gives a different state and other answers."""
    cols = EdgeStream(PARAMS, 13).extend(1).slice(0, 20000)
    ref = Reference(cfg(64, 4096), cols)
    ctl = Reference(dict(cfg(64, 4096), F=512), cols)
    assert np.sum(ref.state()["key"] != ctl.state()["key"]) > 0
    i = np.arange(0, 20000, 37)
    a = ref.vertices(cols["src"][i], cols["src_label"][i], None, "out",
                     np.full(len(i), 20000))
    b = ctl.vertices(cols["src"][i], cols["src_label"][i], None, "out",
                     np.full(len(i), 20000))
    assert np.sum(a != b) > 0
