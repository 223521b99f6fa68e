"""Batched edge queries on window-reduced planes: probe walk + pool path.

``edge_query_planes`` is the composable middle of the "pallas" (planes)
query path (DESIGN.md §8): it takes pre-reduced ``QueryPlanes``
(shard-stacked) plus a query batch and answers every query against every
shard — the matrix probe walk (``sketch_query_xla``: ``s * 2`` gathered
candidates per query and a static first-stop argmax), plus the vectorized
pool lookup for all-occupied-mismatch queries. It is one compiled XLA
program on every backend: a query touches a few random cells of planes
far larger than VMEM, which XLA's gathers fetch from HBM directly.
``repro.sketch.query`` routes through it; ``edge_query_pallas`` is the
standalone single-sketch drop-in kept for tests and direct use.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import hashing as hsh
from repro.core.lsketch import edge_probes, precompute
from repro.core.queries import QueryPlanes, build_query_planes
from repro.core.types import EMPTY, LSketchConfig, LSketchState

__all__ = ["edge_query_planes", "edge_query_pallas", "sketch_query_xla"]


def sketch_query_xla(rows, cols, keys, le_idx, key_plane, cw, pw):
    """The ordered probe walk on window-reduced planes, vectorized over
    shards x queries (integer adds/selects only).

    rows/cols/keys: [nq, s]; le_idx: [nq] or None (skip the label plane);
    key_plane/cw: [S, 2, d, d]; pw: [S, 2, d, d, c].
    Returns (w [S, nq], w_label [S, nq], go_pool [S, nq]).

    The walk needs no loop at all: per query the s*2 candidates are
    gathered in paper order (probe-major, twin-minor) and the first
    (match | empty) is a static argmax — the same formulation as the
    dense reference, but on window-reduced planes (no ``k`` axis rides
    the gathers). Traced (not jitted) — compose inside a jitted caller.
    """
    S = key_plane.shape[0]
    nq, s = rows.shape
    # [S, nq, s, 2] candidates in paper order
    cur = key_plane[:, :, rows, cols]  # [S, 2, nq, s]
    cur = jnp.moveaxis(cur, 1, -1)  # [S, nq, s, 2]
    is_m = (cur == keys[None, :, :, None]).reshape(S, nq, s * 2)
    is_e = (cur == EMPTY).reshape(S, nq, s * 2)
    stop = is_m | is_e
    any_stop = stop.any(-1)
    first = jnp.argmax(stop, -1)  # [S, nq]
    hit = jnp.take_along_axis(is_m, first[..., None], -1)[..., 0] & any_stop
    pi, tz = first // 2, first % 2
    rr = jnp.take_along_axis(jnp.broadcast_to(rows, (S, nq, s)),
                             pi[..., None], -1)[..., 0]
    cc = jnp.take_along_axis(jnp.broadcast_to(cols, (S, nq, s)),
                             pi[..., None], -1)[..., 0]
    s_idx = jnp.arange(S, dtype=jnp.int32)[:, None]
    w = jnp.where(hit, cw[s_idx, tz, rr, cc], 0)
    if le_idx is None:
        wl = jnp.zeros_like(w)
    else:
        wl = jnp.where(hit, pw[s_idx, tz, rr, cc,
                               le_idx[None, :].astype(jnp.int32)], 0)
    return w, wl, ~any_stop


def edge_query_planes(cfg: LSketchConfig, planes: QueryPlanes, src, dst,
                      labels, with_le: bool = True,
                      axis_name: str | None = None):
    """Batched edge queries on window-reduced planes, all shards at once.

    src/dst: int32 [B]; labels: (lA, lB, le) int32 [B] each (``le`` is
    ignored when ``with_le`` is False). Returns (w, w_label), each
    [S, B] — per-shard partials; the caller sums over the shard axis
    (hash partitioning makes shard estimates disjoint).

    Traced (not jitted) — compose inside a jitted caller.

    ``axis_name`` makes this a ``shard_map``-compatible entry point
    (DESIGN.md §9): the planes then carry only the device-local shard
    block ``[S_local, ...]`` and the outputs come back reduced to ``[B]``
    via ``core.merge.psum_partials`` (local sum + cross-device psum) —
    the collective query's one reduction point.

    Horizon-stacked ``MultiPlanes`` (5-dim ``cw``, DESIGN.md §14) are
    accepted via the same leading-axis collapse the shard stack uses:
    ``[H, S, ...]`` reshapes to ``[H*S, ...]``, the walk runs once, and
    the partials fold back per horizon. The multi outputs come back
    ``[H, B]`` ALREADY shard-reduced (psum-reduced too under
    ``axis_name``) — callers must not re-sum a shard axis.
    """
    if planes.cw.ndim == 5:  # horizon-stacked MultiPlanes
        H, S = planes.cw.shape[:2]
        flat = jax.tree.map(
            lambda x: jnp.reshape(x, (H * S,) + x.shape[2:]), planes)
        w, wl = edge_query_planes(cfg, flat, src, dst, labels,
                                  with_le=with_le)
        w = jnp.sum(w.reshape((H, S) + w.shape[1:]), axis=1)
        wl = jnp.sum(wl.reshape((H, S) + wl.shape[1:]), axis=1)
        if axis_name is not None:
            w = jax.lax.psum(w, axis_name)
            wl = jax.lax.psum(wl, axis_name)
        return w, wl
    la, lb, le = labels
    pa = precompute(cfg, src, la)
    pb = precompute(cfg, dst, lb)
    pr = edge_probes(cfg, pa, pb)
    le_idx = hsh.edge_label_bucket(le, cfg.c, cfg.seed) if with_le else None
    S = planes.cw.shape[0]

    w, wl, go_pool = sketch_query_xla(pr.rows, pr.cols, pr.keys, le_idx,
                                      planes.key, planes.cw, planes.pw)

    # pool lookup for all-occupied-mismatch queries (vectorized, per shard)
    ps = hsh.pool_slot_seq(pr.pid_src, pr.pid_dst, cfg.pool_capacity,
                           cfg.pool_probes, cfg.seed)  # [B, probes]
    pk = planes.pool_key[:, ps]  # [S, B, probes, 2]
    pmatch = (pk[..., 0] == pr.pid_src[None, :, None]) & \
        (pk[..., 1] == pr.pid_dst[None, :, None])
    pany = pmatch.any(-1)  # [S, B]
    pfirst = jnp.argmax(pmatch, -1)
    pslot = jnp.take_along_axis(jnp.broadcast_to(ps, (S,) + ps.shape),
                                pfirst[..., None], -1)[..., 0]  # [S, B]
    s_idx = jnp.arange(S, dtype=jnp.int32)[:, None]
    sel = go_pool & pany
    w = w + jnp.where(sel, planes.pool_cw[s_idx, pslot], 0)
    if le_idx is not None:
        wl_p = planes.pool_pw[s_idx, pslot, le_idx[None, :].astype(jnp.int32)]
        wl = wl + jnp.where(sel, wl_p, 0)
    from repro.core.merge import maybe_psum_partials
    return maybe_psum_partials(w, wl, axis_name)


@functools.partial(jax.jit, static_argnums=(0, 5))
def edge_query_pallas(cfg: LSketchConfig, state: LSketchState, src, dst,
                      labels, last: int | None = None):
    """Planes-path equivalent of ``repro.core.edge_query`` (both outputs)
    for one plain sketch state."""
    lifted = jax.tree.map(lambda x: x[None], state)
    planes = build_query_planes(cfg, lifted, last)
    w, wl = edge_query_planes(cfg, planes, src, dst, labels, with_le=True)
    return w[0], wl[0]
