"""Vertex/label aggregate queries on window-reduced planes (out/in, pool).

``vertex_query_planes`` and ``label_aggregate_planes`` are the vertex-side
middles of the "pallas" (planes) query path (DESIGN.md §8), operating on
pre-reduced shard-stacked ``QueryPlanes``. Both are compiled XLA programs
on every backend:

  * vertex aggregates run the r-row masked scan (``vertex_scan_xla``):
    r gathered rows per query, far fewer bytes than the planes, which do
    not fit VMEM at deployment widths;
  * label aggregates are a dense masked reduction over the planes (the
    plane cache is the win).

``vertex_query_pallas`` is the standalone single-sketch drop-in kept for
tests and direct use.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import hashing as hsh
from repro.core.lsketch import precompute
from repro.core.queries import QueryPlanes, build_query_planes
from repro.core.types import EMPTY, LSketchConfig, LSketchState

__all__ = ["vertex_query_planes", "label_aggregate_planes",
           "vertex_query_pallas", "vertex_scan_xla"]


def vertex_scan_xla(lines, f, le_idx, key_plane, cw, pw, *, r: int, F: int,
                    direction: str = "out"):
    """The r-row masked scan (paper Alg. 4) on window-reduced planes:
    one static unroll over the r candidate rows, each iteration gathering
    one row (or, for ``direction="in"``, one column, decoding the
    destination key fields (i_B, f_B)) for all shards x queries. Peak
    transient is [S, 2, B, d(, c)] — the label axis never multiplies the
    r axis. Integer adds only.

    lines: [nq, r] absolute candidate rows (out) / cols (in); f/le_idx:
    [nq] (le_idx None skips the label plane); key_plane/cw: [S, 2, d, d];
    pw: [S, 2, d, d, c]. Returns (w [S, nq], w_label [S, nq]).
    Traced (not jitted) — compose inside a jitted caller.
    """
    S = key_plane.shape[0]
    nq = lines.shape[0]
    w = jnp.zeros((S, nq), cw.dtype)
    wl = jnp.zeros((S, nq), pw.dtype)
    for i in range(r):  # static unroll: peak transient [S, 2, nq, d(, c)]
        li = lines[:, i]  # [nq]
        if direction == "out":
            kg = key_plane[:, :, li]  # [S, 2, nq, d]
            cg = cw[:, :, li]
        else:
            kg = jnp.moveaxis(key_plane[:, :, :, li], 3, 2)  # -> [S, 2, nq, d]
            cg = jnp.moveaxis(cw[:, :, :, li], 3, 2)
        ia, ib, fa, fb = hsh.unpack_key(kg, F)
        idx, fp = (ia, fa) if direction == "out" else (ib, fb)
        match = (kg != EMPTY) & (idx == i) & (fp == f[None, :, None])
        w = w + jnp.sum(jnp.where(match, cg, 0), axis=(1, 3))
        if le_idx is not None:
            if direction == "out":
                pg = pw[:, :, li]  # [S, 2, nq, d, c]
            else:
                pg = jnp.moveaxis(pw[:, :, :, li], 3, 2)
            pl_sel = jnp.take_along_axis(
                pg, le_idx[None, None, :, None, None].astype(jnp.int32),
                -1)[..., 0]  # [S, 2, nq, d]
            wl = wl + jnp.sum(jnp.where(match, pl_sel, 0), axis=(1, 3))
    return w, wl


def vertex_query_planes(cfg: LSketchConfig, planes: QueryPlanes, vertex,
                        labels, direction: str = "out", with_le: bool = True,
                        axis_name: str | None = None):
    """Batched vertex aggregate queries on window-reduced planes.

    vertex: int32 [B]; labels: (lv, le) int32 [B] each (``le`` ignored when
    ``with_le`` is False). Returns (w, w_label), each [S, B] per-shard
    partials. ``axis_name`` as in ``edge_query_planes`` makes this a
    ``shard_map``-compatible entry point returning ``[B]`` outputs reduced
    via ``core.merge.psum_partials`` (DESIGN.md §9).
    Traced — compose inside a jitted caller.

    Horizon-stacked ``MultiPlanes`` (5-dim ``cw``, DESIGN.md §14) collapse
    their leading ``[H]`` into the shard axis, scan once, and return
    ``[H, B]`` ALREADY shard-reduced (and psum-reduced under
    ``axis_name``) — callers must not re-sum a shard axis.
    """
    if planes.cw.ndim == 5:  # horizon-stacked MultiPlanes
        H, S = planes.cw.shape[:2]
        flat = jax.tree.map(
            lambda x: jnp.reshape(x, (H * S,) + x.shape[2:]), planes)
        w, wl = vertex_query_planes(cfg, flat, vertex, labels,
                                    direction=direction, with_le=with_le)
        w = jnp.sum(w.reshape((H, S) + w.shape[1:]), axis=1)
        wl = jnp.sum(wl.reshape((H, S) + wl.shape[1:]), axis=1)
        if axis_name is not None:
            w = jax.lax.psum(w, axis_name)
            wl = jax.lax.psum(wl, axis_name)
        return w, wl
    lv, le = labels
    pre = precompute(cfg, vertex, lv)
    le_idx = hsh.edge_label_bucket(le, cfg.c, cfg.seed) if with_le else None
    pos = (pre.s[:, None] + pre.offs) % pre.width[:, None]
    lines = pre.start[:, None] + pos  # [B, r] absolute row (or col) index
    S = planes.cw.shape[0]

    w, wl = vertex_scan_xla(lines, pre.f, le_idx, planes.key, planes.cw,
                            planes.pw, r=cfg.r, F=cfg.F, direction=direction)

    # pool contribution: match the stored endpoint id, per shard
    col = 0 if direction == "out" else 1
    pm = planes.pool_key[:, :, col][:, None, :] == pre.vid[None, :, None]
    w = w + jnp.sum(jnp.where(pm, planes.pool_cw[:, None, :], 0), -1)
    if le_idx is not None:
        B = pre.vid.shape[0]
        lw = jnp.take_along_axis(
            jnp.broadcast_to(planes.pool_pw[:, None],
                             (S, B) + planes.pool_pw.shape[1:]),
            le_idx[None, :, None, None].astype(jnp.int32), -1)[..., 0]
        wl = wl + jnp.sum(jnp.where(pm, lw, 0), -1)
    from repro.core.merge import maybe_psum_partials
    return maybe_psum_partials(w, wl, axis_name)


def label_aggregate_planes(cfg: LSketchConfig, planes: QueryPlanes, vlabel,
                           edge_label=None, direction: str = "out",
                           with_le: bool = False,
                           axis_name: str | None = None):
    """Vertex-label aggregates on window-reduced planes (Alg. 4 lines
    10-14): sum every occupied cell in the label's block rows (out) /
    columns (in) plus matching pool entries. Returns (w, w_label) [S, B],
    or ``[B]`` psum-reduced when ``axis_name`` is set (the shard_map
    collective entry, DESIGN.md §9). Horizon-stacked ``MultiPlanes``
    (5-dim ``cw``) collapse like the other plane ops and return ``[H, B]``
    ALREADY shard-reduced — callers must not re-sum a shard axis.
    """
    if planes.cw.ndim == 5:  # horizon-stacked MultiPlanes (DESIGN.md §14)
        H, S = planes.cw.shape[:2]
        flat = jax.tree.map(
            lambda x: jnp.reshape(x, (H * S,) + x.shape[2:]), planes)
        w, wl = label_aggregate_planes(cfg, flat, vlabel,
                                       edge_label=edge_label,
                                       direction=direction, with_le=with_le)
        w = jnp.sum(w.reshape((H, S) + w.shape[1:]), axis=1)
        wl = jnp.sum(wl.reshape((H, S) + wl.shape[1:]), axis=1)
        if axis_name is not None:
            w = jax.lax.psum(w, axis_name)
            wl = jax.lax.psum(wl, axis_name)
        return w, wl
    vlabel = jnp.asarray(vlabel, jnp.int32)
    B = vlabel.shape[0]
    S = planes.cw.shape[0]
    le_idx = hsh.edge_label_bucket(edge_label, cfg.c, cfg.seed) \
        if with_le else None
    starts, widths = cfg.block_start_width()
    m = hsh.vertex_label_block(vlabel, cfg.n_blocks, cfg.seed)
    rows = jnp.arange(cfg.d, dtype=jnp.int32)
    in_block = (rows[None, :] >= starts[m][:, None]) & (
        rows[None, :] < (starts[m] + widths[m])[:, None])  # [B, d]
    occ = planes.key != EMPTY  # [S, 2, d, d]
    cell_tot = planes.cw * occ
    axis_tot = cell_tot.sum(axis=(1, 3)) if direction == "out" \
        else cell_tot.sum(axis=(1, 2))  # [S, d]
    w = jnp.sum(in_block[None] * axis_tot[:, None, :], -1)  # [S, B]
    wl = jnp.zeros_like(w)
    if with_le:
        Pc = planes.pw * occ[..., None]
        per_lbl = Pc.sum(axis=(1, 3)) if direction == "out" \
            else Pc.sum(axis=(1, 2))  # [S, d, c]
        lw = jnp.take_along_axis(
            jnp.broadcast_to(per_lbl[:, None], (S, B) + per_lbl.shape[1:]),
            le_idx[None, :, None, None].astype(jnp.int32), -1)[..., 0]
        wl = jnp.sum(in_block[None] * lw, -1)
    # pool: endpoint block id stored inside the packed vid
    col = 0 if direction == "out" else 1
    pcol = planes.pool_key[:, :, col]  # [S, Q]
    pm_blocks, _, _ = hsh.unpack_vertex_id(pcol, cfg.F)
    pmatch = (pcol != EMPTY)[:, None, :] & \
        (pm_blocks[:, None, :] == m[None, :, None])  # [S, B, Q]
    w = w + jnp.sum(jnp.where(pmatch, planes.pool_cw[:, None, :], 0), -1)
    if with_le:
        plw = jnp.take_along_axis(
            jnp.broadcast_to(planes.pool_pw[:, None],
                             (S, B) + planes.pool_pw.shape[1:]),
            le_idx[None, :, None, None].astype(jnp.int32), -1)[..., 0]
        wl = wl + jnp.sum(jnp.where(pmatch, plw, 0), -1)
    from repro.core.merge import maybe_psum_partials
    return maybe_psum_partials(w, wl, axis_name)


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def vertex_query_pallas(cfg: LSketchConfig, state: LSketchState, vertex,
                        labels, direction: str = "out",
                        last: int | None = None):
    """Planes-path equivalent of ``repro.core.vertex_query`` for one
    plain sketch state."""
    lifted = jax.tree.map(lambda x: x[None], state)
    planes = build_query_planes(cfg, lifted, last)
    w, wl = vertex_query_planes(cfg, planes, vertex, labels,
                                direction=direction, with_le=True)
    return w[0], wl[0]
