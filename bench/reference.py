"""Plain reference for the LSketch served path: the same operations on the
same stream give the same state and the same answers.

It imports nothing of the program. The hash family (murmur3 finalizer,
linear-congruence candidate lists, sampled probe pairs, the pool's probe
sequence) is copied in numpy from the paper's definitions as the repository
states them, and the semantics are rebuilt from the paper's Algorithms 1-4:

* an edge's endpoints address a label block, an address ``s`` and a
  fingerprint ``f``; the pair ``(vid_a, vid_b)`` of packed endpoint
  identities decides everything that follows, because a cell's position
  and stored key determine both identities exactly;
* every item lands where its pair's first item landed: the first of its
  ``s`` sampled cells x 2 twins (probe-major) that was empty or held its
  key at that moment, else the first of ``pool_probes`` pool slots that was
  empty or held its pair, else nowhere (its weight is counted lost).
  Keys are never removed, so this first-fit claim is made once per pair,
  in the order of each pair's first item (``first_fit``);
* counters: ring slot ``widx % k`` holds the newest subwindow that mapped
  to it; a query at stream position ``n`` with horizon ``h`` sums the items
  before ``n`` whose subwindow lies in the newest ``h`` (``cur - h, cur]``;
* an edge query answers its pair's weight; a vertex query the weight of
  every stored item whose source (out) or destination (in) identity is the
  vertex's; a label aggregate that of every stored item whose source (out)
  or destination (in) lies in the label's block. An edge label restricts
  each to the items in its label bucket.

``sequential_state`` is the literal item-by-item replay of the same rules,
kept for the tests that hold ``first_fit`` to it.
"""

from __future__ import annotations

import numpy as np

EMPTY = -1
NEVER = -(2 ** 30)
IDX_RADIX = 16
VID_RADIX = 2048  # packed vertex identity: (m * 2048 + s) * F + f
U32 = np.uint32
M31 = U32(0x7FFFFFFF)
LCG_T, LCG_I = U32(1103515245), U32(12345)


# --------------------------------------------------------------------------
# hash family (uint32 arithmetic, wrapping)
# --------------------------------------------------------------------------

def mix32(x, seed: int) -> np.ndarray:
    h = np.asarray(x).astype(U32) ^ U32(seed & 0xFFFFFFFF)
    h ^= h >> U32(16)
    h *= U32(0x85EBCA6B)
    h ^= h >> U32(13)
    h *= U32(0xC2B2AE35)
    h ^= h >> U32(16)
    return h


def hash31(x, seed: int) -> np.ndarray:
    return (mix32(x, seed) & M31).astype(np.int64)


def lcg_next(x) -> np.ndarray:
    return (LCG_T * np.asarray(x).astype(U32) + LCG_I) & M31


def candidate_offsets(f, r: int) -> np.ndarray:
    """l_1..l_r from fingerprint f: int64 [..., r]."""
    out, x = [], lcg_next(f)
    for _ in range(r):
        out.append(x.astype(np.int64))
        x = lcg_next(x)
    return np.stack(out, -1)


def sample_pairs(fa, fb, r: int, s: int):
    """Probe subscripts (A_i, B_i), i = 1..s: int64 [..., s] each."""
    ai, bi = [], []
    x = lcg_next(np.asarray(fa).astype(U32) + np.asarray(fb).astype(U32))
    for _ in range(s):
        xi = x.astype(np.int64)
        ai.append((xi // r) % r)
        bi.append(xi % r)
        x = lcg_next(x)
    return np.stack(ai, -1), np.stack(bi, -1)


def pool_slots(vid_a, vid_b, q: int, probes: int, seed: int) -> np.ndarray:
    x = (np.asarray(vid_a).astype(U32) * U32(0x9E3779B9)) \
        ^ np.asarray(vid_b).astype(U32)
    base = (mix32(x, seed ^ 0x0031) & M31).astype(np.int64) % q
    return (base[..., None] + np.arange(probes)) % q


class Sketch:
    """The configuration's static sizes (uniform label blocks)."""

    def __init__(self, cfg: dict):
        self.d = int(cfg["d"])
        self.n_blocks = int(cfg["n_blocks"])
        self.b = self.d // self.n_blocks
        self.F = int(cfg["F"])
        self.r = int(cfg["r"])
        self.s = int(cfg["s"])
        self.c = int(cfg["c"])
        self.k = int(cfg["k"])
        self.sub = max(1, int(cfg["window_size"]) // self.k)
        self.q = int(cfg["pool_capacity"])
        self.probes = int(cfg["pool_probes"])
        self.seed = int(cfg["seed"])

    def block(self, label) -> np.ndarray:
        return hash31(label, self.seed ^ 0x5B1D) % self.n_blocks

    def bucket(self, edge_label) -> np.ndarray:
        return hash31(edge_label, self.seed ^ 0x77E1) % self.c

    def vid(self, v, label) -> np.ndarray:
        m = self.block(label)
        h = hash31(v, self.seed)
        return (m * VID_RADIX + (h // self.F) % self.b) * self.F + h % self.F

    def unpack(self, vid):
        vid = np.asarray(vid, np.int64)
        f = vid % self.F
        rest = vid // self.F
        return rest // VID_RADIX, rest % VID_RADIX, f  # m, s, f

    def probes_of(self, vid_a, vid_b):
        """Matrix candidates of pairs: cell ids ``(row*d + col)*2 + twin``
        and packed keys, both int64 [n, 2s] in probe-major order."""
        ma, sa, fa = self.unpack(vid_a)
        mb, sb, fb = self.unpack(vid_b)
        ai, bi = sample_pairs(fa, fb, self.r, self.s)
        offa = np.take_along_axis(candidate_offsets(fa, self.r), ai, -1)
        offb = np.take_along_axis(candidate_offsets(fb, self.r), bi, -1)
        rows = ma[:, None] * self.b + (sa[:, None] + offa) % self.b
        cols = mb[:, None] * self.b + (sb[:, None] + offb) % self.b
        keys = ((ai * IDX_RADIX + bi) * self.F + fa[:, None]) * self.F \
            + fb[:, None]
        cell = (rows * self.d + cols) * 2
        cells = np.stack([cell, cell + 1], -1).reshape(len(ma), -1)
        keys = np.repeat(keys, 2, axis=-1)
        return cells, keys


def first_fit(cand: np.ndarray, n_slots: int) -> np.ndarray:
    """Sequential first-fit over slots that, once taken, stay taken.

    Row ``i`` (in arrival order) takes the first of its candidate slots
    that no earlier row holds. Returns each row's position in its
    candidate list, or -1 where every candidate was taken.

    Solved as deferred acceptance: rows propose down their lists and a
    slot keeps its earliest proposer. With one ranking shared by every
    slot (arrival order) the stable matching is unique and equals the
    sequential greedy, so the result is the item-by-item replay's
    (``sequential_state`` holds it to that in the tests)."""
    n, m = cand.shape
    ptr = np.zeros(n, np.int64)
    owner = np.full(n_slots, n, np.int64)
    free = np.arange(n, dtype=np.int64)
    while free.size:
        c = cand[free, ptr[free]]
        order = np.lexsort((free, c))
        cs, fs = c[order], free[order]
        head = np.ones(len(cs), bool)
        head[1:] = cs[1:] != cs[:-1]
        hi = np.flatnonzero(head)
        uc, win = cs[hi], fs[hi]
        held = owner[uc]
        take = win < held
        owner[uc[take]] = win[take]
        accepted = np.zeros(len(fs), bool)
        accepted[hi[take]] = True
        bumped = held[take]
        again = np.concatenate([fs[~accepted], bumped[bumped < n]])
        ptr[again] += 1
        free = again[ptr[again] < m]
    pos = np.where(ptr < m, ptr, -1)
    rows = np.arange(n)
    ok = pos >= 0
    ok[ok] = owner[cand[rows[ok], pos[ok]]] == rows[ok]
    return np.where(ok, pos, -1)


class Reference:
    """The reference over the first ``len(cols["src"])`` items of a stream.

    ``cols``: the stream's int columns (src, dst, src_label, dst_label,
    edge_label, weight, time), in ingest order."""

    def __init__(self, cfg: dict, cols: dict):
        sk = self.sk = Sketch(cfg)
        self.n = n = len(cols["src"])
        self.w = np.asarray(cols["weight"], np.int64)
        self.widx = np.asarray(cols["time"], np.int64) // sk.sub
        self.bucket = sk.bucket(cols["edge_label"])
        self.vid_a = sk.vid(cols["src"], cols["src_label"])
        self.vid_b = sk.vid(cols["dst"], cols["dst_label"])
        self.m_a = sk.unpack(self.vid_a)[0]
        self.m_b = sk.unpack(self.vid_b)[0]
        code = self.vid_a * (1 << 24) + self.vid_b
        uniq, first, inv = np.unique(code, return_index=True,
                                     return_inverse=True)
        order = np.argsort(first, kind="stable")  # pairs by first arrival
        rank = np.empty_like(order)
        rank[order] = np.arange(len(order))
        self.pair = rank[inv]  # per item: pair id in arrival order
        pa = self.vid_a[first[order]]
        pb = self.vid_b[first[order]]
        self.pair_a, self.pair_b = pa, pb
        cells, keys = sk.probes_of(pa, pb)
        pos = first_fit(cells, sk.d * sk.d * 2)
        idx = np.arange(len(pa))
        self.pair_cell = np.where(pos >= 0, cells[idx, np.maximum(pos, 0)],
                                  -1)
        self.pair_key = np.where(pos >= 0, keys[idx, np.maximum(pos, 0)],
                                 EMPTY)
        self.pair_pool = np.full(len(pa), -1, np.int64)
        rest = np.flatnonzero(pos < 0)
        if rest.size:
            cand = pool_slots(pa[rest], pb[rest], sk.q, sk.probes, sk.seed)
            ppos = first_fit(cand, sk.q)
            got = ppos >= 0
            self.pair_pool[rest[got]] = cand[got, ppos[got]]
        self.pair_stored = (self.pair_cell >= 0) | (self.pair_pool >= 0)
        self.stored = self.pair_stored[self.pair]
        self._index = {}

    # ---- state ----------------------------------------------------------

    def state(self) -> dict:
        """The program's state leaves after every item, shard axis dropped:
        dense ``key``, ``pool_key``, ``pool_C``, ``pool_P``, ``pool_lost``,
        ``slot_widx``, ``cur_widx``, ``C``; ``P`` as sorted nonzero
        ``(flat index, value)`` pairs (it is mostly zeros)."""
        sk = self.sk
        d2, k, c, q = sk.d * sk.d * 2, sk.k, sk.c, sk.q
        key = np.full(d2, EMPTY, np.int64)
        m = self.pair_cell >= 0
        key[self.pair_cell[m]] = self.pair_key[m]
        pool_key = np.full((q, 2), EMPTY, np.int64)
        pm = self.pair_pool >= 0
        pool_key[self.pair_pool[pm], 0] = self.pair_a[pm]
        pool_key[self.pair_pool[pm], 1] = self.pair_b[pm]
        slot_widx = np.full(k, NEVER, np.int64)
        np.maximum.at(slot_widx, self.widx % k, self.widx)
        live = self.widx == slot_widx[self.widx % k]
        slot = self.widx % k
        cell = self.pair_cell[self.pair]
        pool = self.pair_pool[self.pair]
        mi = live & (cell >= 0)
        C = np.bincount(cell[mi] * k + slot[mi], self.w[mi], d2 * k)
        pi = live & (pool >= 0)
        pool_C = np.bincount(pool[pi] * k + slot[pi], self.w[pi], q * k)
        pool_P = np.bincount((pool[pi] * k + slot[pi]) * c
                             + self.bucket[pi], self.w[pi], q * k * c)
        pflat = (cell[mi] * k + slot[mi]) * c + self.bucket[mi]
        pidx, pinv = np.unique(pflat, return_inverse=True)
        pval = np.bincount(pinv, self.w[mi], len(pidx))
        return {"key": key, "C": C.astype(np.int64),
                "P": (pidx, pval.astype(np.int64)),
                "pool_key": pool_key.ravel(),
                "pool_C": pool_C.astype(np.int64),
                "pool_P": pool_P.astype(np.int64),
                "pool_lost": np.array([self.w[~self.stored].sum()]),
                "slot_widx": slot_widx,
                "cur_widx": np.array([self.widx.max() if self.n else NEVER])}

    # ---- queries --------------------------------------------------------

    def _sorted(self, name: str, key: np.ndarray):
        """Items stored in the sketch, sorted by (key, position), with a
        cumulative weight: the sum over positions [lo, hi) of one key is
        two binary searches."""
        if name not in self._index:
            pos = np.flatnonzero(self.stored)
            comp = key[pos] * self.n + pos
            o = np.argsort(comp, kind="stable")
            cum = np.concatenate([[0], np.cumsum(self.w[pos][o])])
            self._index[name] = (comp[o], cum)
        return self._index[name]

    def _range_sum(self, name, key_fn, keys, lo, hi):
        comp, cum = self._sorted(name, key_fn())
        keys = np.asarray(keys, np.int64)
        a = np.searchsorted(comp, keys * self.n + lo)
        b = np.searchsorted(comp, keys * self.n + hi)
        return cum[b] - cum[a]

    def _window(self, n_at, last):
        """Positions [lo, n_at) counted by a query at stream position
        ``n_at`` with horizon ``last`` (None: the whole window)."""
        n_at = np.asarray(n_at, np.int64)
        h = self.sk.k if last is None else min(int(last), self.sk.k)
        cur = self.widx[np.maximum(n_at - 1, 0)]
        lo = np.searchsorted(self.widx, cur - h, side="right")
        return np.where(n_at > 0, lo, 0), n_at

    def edges(self, src, la, dst, lb, le, n_at, last=None):
        """``le``: None, or an edge label per query."""
        sk = self.sk
        va, vb = sk.vid(src, la), sk.vid(dst, lb)
        code = va * (1 << 24) + vb
        pair_code = self.pair_a * (1 << 24) + self.pair_b
        o = np.argsort(pair_code)
        j = np.searchsorted(pair_code[o], code)
        j = np.minimum(j, len(o) - 1)
        found = pair_code[o][j] == code
        pid = np.where(found, o[j], 0)
        lo, hi = self._window(n_at, last)
        if le is None:
            out = self._range_sum("pair", lambda: self.pair, pid, lo, hi)
        else:
            out = self._range_sum(
                "pair_bucket", lambda: self.pair * self.sk.c + self.bucket,
                pid * self.sk.c + sk.bucket(le), lo, hi)
        return np.where(found, out, 0)

    def vertices(self, v, lv, le, direction, n_at, last=None):
        sk = self.sk
        vid = sk.vid(v, lv)
        col = self.vid_a if direction == "out" else self.vid_b
        lo, hi = self._window(n_at, last)
        if le is None:
            return self._range_sum("v" + direction, lambda: col, vid, lo, hi)
        return self._range_sum(
            "vb" + direction, lambda: col * sk.c + self.bucket,
            vid * sk.c + sk.bucket(le), lo, hi)

    def labels(self, lv, le, direction, n_at, last=None):
        sk = self.sk
        m = sk.block(lv)
        col = self.m_a if direction == "out" else self.m_b
        lo, hi = self._window(n_at, last)
        if le is None:
            return self._range_sum("l" + direction, lambda: col, m, lo, hi)
        return self._range_sum(
            "lb" + direction, lambda: col * sk.c + self.bucket,
            m * sk.c + sk.bucket(le), lo, hi)


def sequential_state(cfg: dict, cols: dict) -> dict:
    """Item-by-item replay of the insertion rules (paper Algorithm 2 with
    the additional pool and the lazy ring), for the tests. Returns the
    dense ``key``, ``C``, ``P``, ``pool_*`` leaves (flat), ``pool_lost``."""
    sk = Sketch(cfg)
    d2, k, c, q = sk.d * sk.d * 2, sk.k, sk.c, sk.q
    key = np.full(d2, EMPTY, np.int64)
    C = np.zeros((d2, k), np.int64)
    P = np.zeros((d2, k, c), np.int64)
    pool_key = np.full((q, 2), EMPTY, np.int64)
    pool_C = np.zeros((q, k), np.int64)
    pool_P = np.zeros((q, k, c), np.int64)
    slot_widx = np.full(k, NEVER, np.int64)
    lost = 0
    va = sk.vid(cols["src"], cols["src_label"])
    vb = sk.vid(cols["dst"], cols["dst_label"])
    cells, keys = sk.probes_of(va, vb)
    pslots = pool_slots(va, vb, q, sk.probes, sk.seed)
    bucket = sk.bucket(cols["edge_label"])
    for i in range(len(va)):
        widx = int(cols["time"][i]) // sk.sub
        slot = widx % k
        if widx < slot_widx[slot]:
            continue  # an expired item (never in a time-ordered stream)
        if widx > slot_widx[slot]:
            slot_widx[slot] = widx
            C[:, slot] = 0
            P[:, slot] = 0
            pool_C[:, slot] = 0
            pool_P[:, slot] = 0
        w = int(cols["weight"][i])
        for cell, kk in zip(cells[i], keys[i]):
            if key[cell] in (EMPTY, kk):
                key[cell] = kk
                C[cell, slot] += w
                P[cell, slot, bucket[i]] += w
                break
        else:
            for ps in pslots[i]:
                if pool_key[ps, 0] == EMPTY or (
                        pool_key[ps, 0] == va[i] and pool_key[ps, 1] == vb[i]):
                    pool_key[ps] = (va[i], vb[i])
                    pool_C[ps, slot] += w
                    pool_P[ps, slot, bucket[i]] += w
                    break
            else:
                lost += w
    return {"key": key, "C": C.ravel(), "P": P.ravel(),
            "pool_key": pool_key.ravel(), "pool_C": pool_C.ravel(),
            "pool_P": pool_P.ravel(), "pool_lost": np.array([lost]),
            "slot_widx": slot_widx}
