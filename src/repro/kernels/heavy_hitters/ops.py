"""Heavy-hitter / top-k analytics over window-reduced QueryPlanes.

Three entry points — ``heavy_vertices_planes`` / ``heavy_edges_planes`` /
``top_labels_planes``:

  * the cell decode runs on the Pallas kernel on TPU and on its compiled
    XLA twin elsewhere (``repro.kernels.use_tpu_kernels``);
  * ``axis_name=...``: the same body runs inside ``shard_map`` — decode
    and flatten locally, ``all_gather`` the (identity, weight) rows, run
    the replicated epilogue. Per-identity totals are plain integer sums,
    so gather interleaving cannot change results: all paths bit-identical.

Top-k semantics (pinned against the fixed host reference in
``repro.core.analytics``): aggregate every occupied matrix cell *and*
every pool entry by decoded identity, rank by descending windowed weight,
break ties by ascending identity (lexicographic (src, dst) for edges).
Identities are int32 packed vids — edge identity is the *column pair*
(src, dst) ordered lexicographically, deliberately avoiding a packed
64-bit key so nothing here depends on x64 mode. Outputs are fixed-shape
``[k]`` arrays padded with (-1, 0) when fewer than k live identities
exist.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels import use_tpu_kernels
from repro.kernels.heavy_hitters.kernel import (
    EMPTY, cell_decode_kernel_sharded, cell_decode_xla)


def _static_blocks(cfg) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    # pure-Python mirror of cfg.block_start_width() — static even when
    # called mid-trace (kernel grids and unrolls need Python ints)
    if cfg.block_bounds is not None:
        return (tuple(s for s, _ in cfg.block_bounds),
                tuple(w for _, w in cfg.block_bounds))
    return (tuple(i * cfg.b for i in range(cfg.n_blocks)),
            (cfg.b,) * cfg.n_blocks)


def decode_cell_owners(cfg, planes):
    """(vid_src, vid_dst) [S, 2, d, d] — decoded owners of every cell of
    the window-reduced planes, EMPTY (-1) where unoccupied."""
    starts, widths = _static_blocks(cfg)
    if use_tpu_kernels():
        return cell_decode_kernel_sharded(planes.key, starts=starts,
                                          widths=widths, r=cfg.r, F=cfg.F)
    return cell_decode_xla(planes.key, starts=jnp.asarray(starts),
                           widths=jnp.asarray(widths), r=cfg.r, F=cfg.F)


def _select_topk(vals, k: int):
    """k successive argmax extractions over ``vals`` (candidate totals,
    dead rows < 0). argmax's first-index tie rule is the ascending-identity
    tie break — callers arrange candidates in ascending identity order.
    Returns (idx [k], totals [k]) with (0-gather-safe idx, 0) padding;
    O(kN) elementwise, far faster on CPU than XLA's variadic top-k."""
    def body(i, carry):
        vals, idx, out = carry
        j = jnp.argmax(vals)
        idx = idx.at[i].set(j)
        out = out.at[i].set(jnp.maximum(vals[j], 0))
        return vals.at[j].set(jnp.int32(-1)), idx, out

    _, idx, out = jax.lax.fori_loop(
        0, k, body, (vals.astype(jnp.int32),
                     jnp.zeros((k,), jnp.int32), jnp.zeros((k,), jnp.int32)))
    return idx, out


def segment_topk(cols, w, k: int):
    """Aggregate rows by identity and take the top-k totals.

    cols: tuple of int32 [N] identity columns (lexicographic significance,
    most significant first); dead rows must carry negatives in *every*
    column. w: [N] int32 weights. Returns (tuple of [k] identity columns,
    [k] totals), descending total, ties ascending identity, (-1, 0)
    padding — deterministic for any row order because per-identity totals
    are order-free integer sums computed after a full sort by identity.

    Single-column identities take a fast path: XLA CPU's single-operand
    sort is ~4x the variadic (comparator-loop) sort, so instead of sorting
    (ident, w) together, sort ident alone, recover each row's group as its
    identity's first-occurrence index (``searchsorted`` into the sorted
    array), and scatter-add the weights onto those group anchors. The
    variadic lexicographic sort only remains for multi-column (edge)
    identities, which cannot be searchsorted.
    """
    w = jnp.where(cols[0] >= 0, w, 0).astype(jnp.int32)
    if len(cols) == 1:
        su = jnp.sort(cols[0].astype(jnp.int32))
        # first-occurrence index of each row's identity: a scatter target
        # that is unique per identity and ascending with it
        seg = jnp.searchsorted(su, cols[0].astype(jnp.int32))
        tot = jnp.zeros_like(w).at[seg].add(w)
        live = (su >= 0) & (tot > 0)
        idx, out_w = _select_topk(jnp.where(live, tot, jnp.int32(-1)), k)
        good = out_w > 0
        return (jnp.where(good, su[idx], jnp.int32(-1)),), out_w
    # one variadic lexicographic sort groups equal identities into runs
    # (ascending); w rides along as a non-key operand
    ops = jax.lax.sort(tuple(c.astype(jnp.int32) for c in cols) + (w,),
                       num_keys=len(cols), is_stable=True)
    sc, sw = list(ops[:-1]), ops[-1]
    neq = sc[0][1:] != sc[0][:-1]
    for c in sc[1:]:
        neq = neq | (c[1:] != c[:-1])
    start = jnp.concatenate([jnp.ones((1,), jnp.bool_), neq])
    end = jnp.concatenate([neq, jnp.ones((1,), jnp.bool_)])
    # per-run totals without scatters (XLA CPU scatter is serial): inclusive
    # cumsum, minus the run's base forward-filled by cummax — run bases are
    # nondecreasing (cumsum is), so max-scan over start-marked bases fills
    cs = jnp.cumsum(sw)
    run_base = jax.lax.cummax(jnp.where(start, cs - sw, 0))
    total = (cs - run_base).astype(jnp.int32)
    # a run's END row carries its full total; every run is one end row, in
    # ascending-lexicographic-identity order, matching _select_topk's tie
    # rule
    live = end & (sc[0] >= 0) & (total > 0)
    idx, out_w = _select_topk(jnp.where(live, total, jnp.int32(-1)), k)
    good = out_w > 0
    out_c = tuple(jnp.where(good, c[idx], jnp.int32(-1)) for c in sc)
    return out_c, out_w


def _flatten_rows(vids, planes, col: int):
    """Per-shard (identity, weight) rows: matrix cells then pool entries.
    vids: [S, 2, d, d] decoded owner side (or None to take pool column
    only via ``col``)."""
    S = planes.cw.shape[0]
    pool_live = planes.pool_cw > 0
    pid = jnp.where(pool_live, planes.pool_key[:, :, col], EMPTY)
    ident = jnp.concatenate([vids.reshape(S, -1), pid], axis=1).reshape(-1)
    w = jnp.concatenate([planes.cw.reshape(S, -1), planes.pool_cw],
                        axis=1).reshape(-1)
    return ident, w


def _gathered(arrs, axis_name):
    if axis_name is None:
        return arrs
    return [jax.lax.all_gather(a, axis_name, tiled=True) for a in arrs]


def heavy_vertices_planes(cfg, planes, k: int, *, direction: str = "out",
                          axis_name=None):
    """Top-k (packed vid [k], weight [k]) by windowed out/in weight."""
    vs, vd = decode_cell_owners(cfg, planes)
    col = 0 if direction == "out" else 1
    ident, w = _flatten_rows(vs if direction == "out" else vd, planes, col)
    ident, w = _gathered([ident, w], axis_name)
    (ids,), ws = segment_topk((ident,), w, k)
    return ids, ws


def heavy_edges_planes(cfg, planes, k: int, *, axis_name=None):
    """Top-k edges by windowed weight: (src [k], dst [k], weight [k])."""
    vs, vd = decode_cell_owners(cfg, planes)
    src, w = _flatten_rows(vs, planes, 0)
    dst, _ = _flatten_rows(vd, planes, 1)
    src, dst, w = _gathered([src, dst, w], axis_name)
    (s, t), ws = segment_topk((src, dst), w, k)
    return s, t, ws


def top_labels_planes(cfg, planes, k: int, *, direction: str = "out",
                      axis_name=None):
    """Top-k (vertex-label block [k], weight [k]) by windowed out/in
    weight — the decoded vid's block id IS the label block."""
    vs, vd = decode_cell_owners(cfg, planes)
    col = 0 if direction == "out" else 1
    vid, w = _flatten_rows(vs if direction == "out" else vd, planes, col)
    # floor division keeps dead rows negative (-1 // span == -1)
    blk = vid // jnp.int32(2048 * cfg.F)
    blk, w = _gathered([blk, w], axis_name)
    (blocks,), ws = segment_topk((blk,), w, k)
    return blocks, ws
