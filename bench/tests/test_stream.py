"""The copied generator: seeded, endless, time-ordered, labels per vertex."""

import numpy as np

from bench.stream import CHUNK, EdgeStream, StreamParams

PARAMS = StreamParams(n_vertices=5000, n_vertex_labels=7, n_edge_labels=11,
                      zipf_a=1.2, duplicate_rate=0.3,
                      edges_per_time_unit=(500000, 2880))


def test_same_seed_same_chunks():
    a = EdgeStream(PARAMS, 2 ** 31 + 17).extend(CHUNK + 10)
    b = EdgeStream(PARAMS, 2 ** 31 + 17).extend(2 * CHUNK)
    for f, col in a.cols.items():
        assert np.array_equal(col, b.cols[f][:len(col)]), f
    c = EdgeStream(PARAMS, 2 ** 31 + 18).extend(CHUNK)
    assert not np.array_equal(a.cols["src"][:CHUNK], c.cols["src"])


def test_labels_fixed_per_vertex():
    s = EdgeStream(PARAMS, 5).extend(2 * CHUNK)
    lab = np.full(PARAMS.n_vertices, -1)
    for v, l in ((s.cols["src"], s.cols["src_label"]),
                 (s.cols["dst"], s.cols["dst_label"])):
        seen = lab[v] >= 0
        assert np.array_equal(lab[v][seen], l[seen])
        lab[v] = l
    assert np.array_equal(lab[lab >= 0], s.vertex_label[lab >= 0])


def test_time_non_decreasing_at_the_source_rate():
    s = EdgeStream(PARAMS, 9).extend(3 * CHUNK)
    t = s.cols["time"].astype(np.int64)
    assert np.all(np.diff(t) >= 0)
    e, u = PARAMS.edges_per_time_unit
    assert t[-1] == (len(t) - 1) * u // e
    assert t.dtype == np.int64 or s.cols["time"].dtype == np.int32


def test_duplicates_repeat_recent_items():
    s = EdgeStream(PARAMS, 3).extend(CHUNK)
    src, dst = s.cols["src"], s.cols["dst"]
    pair = src.astype(np.int64) * PARAMS.n_vertices + dst
    # at 30% duplicates, far more consecutive-window repeats than chance
    repeats = np.mean([pair[i] in set(pair[max(0, i - 499):i])
                       for i in range(1000, 3000)])
    assert repeats > 0.25


def test_picks_are_folded_zipf_draws():
    """Endpoints follow ``(zipf(a) - 1) % n``: inside the vertex range, and
    vertex 0 about as frequent as the folded distribution makes it."""
    s = EdgeStream(PARAMS, 4).extend(CHUNK)
    src = s.cols["src"]
    assert src.min() >= 0 and src.max() < PARAMS.n_vertices
    n = 400000
    z = (np.random.default_rng(1).zipf(PARAMS.zipf_a, n) - 1) \
        % PARAMS.n_vertices
    want = np.mean(z == 0)
    # duplicates repeat earlier picks, so the marginal is unchanged
    assert abs(np.mean(src == 0) - want) < 0.01


def test_tens_of_millions_of_vertices():
    big = StreamParams(n_vertices=65_608_366, n_vertex_labels=20,
                       n_edge_labels=100, zipf_a=1.2, duplicate_rate=0.2,
                       edges_per_time_unit=(500000, 2880))
    s = EdgeStream(big, 2 ** 33 + 5).extend(CHUNK)
    assert s.vertex_label.nbytes == big.n_vertices
    assert s.cols["src"].max() >= 1 << 24  # the tail reaches far ids
    assert s.cols["src_label"].dtype == np.int32
