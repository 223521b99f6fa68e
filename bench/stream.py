"""Seeded, endless, time-ordered labelled edge stream.

A copy of the repository's heterogeneous graph-stream generator
(``repro.data.stream.generate``), kept here so that no change to the
program can move the benchmark's inputs, and extended from a fixed-size
CPU sample to an endless stream:

* source and destination are Zipf-skewed picks folded into
  ``[0, n_vertices)``: ``(rng.zipf(a) - 1) % n``, as the original draws
  them;
* with probability ``duplicate_rate`` an item repeats the raw endpoints
  of an item 1-499 positions earlier (stream locality);
* vertex labels are drawn once per vertex from the seed, so a vertex
  keeps its label for the whole stream;
* edge labels are uniform, weights 1;
* timestamps advance at the source's rate, ``edges_per_time_unit`` as an
  exact fraction ``[edges, time_units]``: item ``i`` has time
  ``i * time_units // edges``. Windows slide and ring slots are
  reclaimed as the stream runs, and every seed has the same boundary
  positions, so seeds change which edges arrive and not how many cross a
  subwindow boundary.

Items are generated in chunks of ``CHUNK`` from independent seed streams
(``[seed, 1, chunk]``), so the first ``n`` items do not depend on how
many more are generated later.
"""

from __future__ import annotations

import dataclasses

import numpy as np

CHUNK = 1 << 20
LOOKBACK = 500  # duplicates repeat an item 1..LOOKBACK-1 positions back
FIELDS = ("src", "dst", "src_label", "dst_label", "edge_label", "weight",
          "time")


def _seed_words(seed: int) -> int:
    """Any whole number (negative or past 64 bits) as a SeedSequence word."""
    return int(seed) % (1 << 64)


@dataclasses.dataclass(frozen=True)
class StreamParams:
    n_vertices: int
    n_vertex_labels: int
    n_edge_labels: int
    zipf_a: float
    duplicate_rate: float
    edges_per_time_unit: tuple  # (edges, time_units), an exact fraction

    @classmethod
    def from_config(cls, d: dict) -> "StreamParams":
        return cls(n_vertices=int(d["n_vertices"]),
                   n_vertex_labels=int(d["n_vertex_labels"]),
                   n_edge_labels=int(d["n_edge_labels"]),
                   zipf_a=float(d["zipf_a"]),
                   duplicate_rate=float(d["duplicate_rate"]),
                   edges_per_time_unit=tuple(
                       int(x) for x in d["edges_per_time_unit"]))


class EdgeStream:
    """The first ``n`` items of the endless stream for ``seed``, as int32
    numpy columns (``FIELDS``). ``extend(n)`` generates more."""

    def __init__(self, params: StreamParams, seed: int, n: int = 0):
        self.params = params
        self.seed = _seed_words(seed)
        rng = np.random.default_rng([self.seed, 0])
        # one byte per vertex where the labels fit: tens of millions of
        # vertices stay a few hundred MB
        small = params.n_vertex_labels <= 256
        self.vertex_label = rng.integers(
            0, params.n_vertex_labels, params.n_vertices,
            dtype=np.uint8 if small else np.int32)
        self._raw_src = np.zeros(0, np.int32)  # pre-duplicate picks
        self._raw_dst = np.zeros(0, np.int32)
        self.cols = {f: np.zeros(0, np.int32) for f in FIELDS}

    def __len__(self) -> int:
        return len(self.cols["src"])

    def _pick(self, rng, n):
        z = rng.zipf(self.params.zipf_a, n)
        return ((z - 1) % self.params.n_vertices).astype(np.int32)

    def _chunk(self, c: int):
        p = self.params
        rng = np.random.default_rng([self.seed, 1, c])
        n = CHUNK
        src = self._pick(rng, n)
        dst = self._pick(rng, n)
        dup = rng.random(n) < p.duplicate_rate
        back = rng.integers(1, LOOKBACK, n)
        edge_label = rng.integers(0, p.n_edge_labels, n).astype(np.int32)
        # a duplicate copies the raw pick ``back`` items earlier, across
        # the chunk boundary too; the stream's first items clamp to 0
        base = c * CHUNK
        prev_s, prev_d = self._raw_src[-LOOKBACK:], self._raw_dst[-LOOKBACK:]
        hist_s = np.concatenate([prev_s, src])
        hist_d = np.concatenate([prev_d, dst])
        off = len(prev_s)
        j = np.maximum(np.arange(n) - back, -base) + off
        out_s = np.where(dup, hist_s[j], src)
        out_d = np.where(dup, hist_d[j], dst)
        return src, dst, out_s, out_d, edge_label

    def extend(self, n: int) -> "EdgeStream":
        """Make sure at least ``n`` items exist."""
        parts = {f: [self.cols[f]] for f in FIELDS}
        have = len(self)
        while have < n:
            c = have // CHUNK
            rs, rd, s, d, le = self._chunk(c)
            self._raw_src = np.concatenate([self._raw_src[-LOOKBACK:], rs])
            self._raw_dst = np.concatenate([self._raw_dst[-LOOKBACK:], rd])
            e, t = self.params.edges_per_time_unit
            idx = np.arange(have, have + CHUNK, dtype=np.int64)
            parts["src"].append(s)
            parts["dst"].append(d)
            parts["src_label"].append(self.vertex_label[s].astype(np.int32))
            parts["dst_label"].append(self.vertex_label[d].astype(np.int32))
            parts["edge_label"].append(le)
            parts["weight"].append(np.ones(CHUNK, np.int32))
            parts["time"].append((idx * t // e).astype(np.int32))
            have += CHUNK
        self.cols = {f: np.concatenate(parts[f]) if len(parts[f]) > 1
                     else parts[f][0] for f in FIELDS}
        return self

    def slice(self, a: int, b: int) -> dict:
        return {f: self.cols[f][a:b] for f in FIELDS}
