"""Handle-layer analytics portfolio (DESIGN.md §12).

Windowed heavy-hitter / top-k queries and batched reachability as
first-class operations on the immutable ``(SketchSpec, ShardedState)``
handle — the reversible-sketch payoff promoted from the host reference
loops in ``repro.core.analytics`` to native-speed array programs over the
same cached ``QueryPlanes`` the query kernels use.

Path contract (same three names as ``query``):

  * ``"scan"``   — dense reference: re-reduce the window planes inside the
    dispatch (no cache).
  * ``"pallas"`` — ``query_planes`` cache.
  * ``"collective"`` — the same body under ``shard_map`` on a
    mesh-resident handle: local decode + flatten, ``all_gather`` of the
    (identity, weight) rows, replicated top-k epilogue.

Every path decodes cell owners on the ``kernels/heavy_hitters`` Pallas
kernel on TPU and on its compiled XLA twin elsewhere.

All three are bit-identical to each other and to the fixed host
reference (pinned in tests/test_analytics.py): per-identity totals are
order-free integer sums and the epilogue's tie order is
(descending weight, ascending identity). ``reachable_many`` is a batched
host BFS (one successor scan per *unique* frontier vertex per hop, shared
across queries) and is exempt from the tri-path contract — it is
host-driven by construction.

Time sensitivity: every top-k honors ``last=`` (the most recent ``last``
subwindows only) through the same horizon-aliasing plane cache as
``query``.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import queries as _cq
from repro.core.lsketch import precompute
from repro.kernels.heavy_hitters.ops import (
    heavy_edges_planes, heavy_vertices_planes, top_labels_planes)

from .query import (_collective_ctx, _count, _lift, _normalize_horizons,
                    _shmap, _shmap_multi, _with_group_window, query_planes,
                    query_planes_multi, resolve_query_path)
from .spec import SketchSpec
from .state import ShardedState, mesh_context


def _planes_topk(cfg, planes, kind: str, k: int, direction: str, *,
                 axis_name=None):
    if kind == "vertex":
        return heavy_vertices_planes(cfg, planes, k, direction=direction,
                                     axis_name=axis_name)
    if kind == "edge":
        return heavy_edges_planes(cfg, planes, k, axis_name=axis_name)
    return top_labels_planes(cfg, planes, k, direction=direction,
                             axis_name=axis_name)


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("kind", "k", "direction", "last",
                                    "stacked"))
def _topk_sharded(spec, shards, *, kind, k, direction, last, stacked=True):
    _count("hh_" + kind, "scan")
    shards = _with_group_window(_lift(shards, stacked))
    planes = _cq.build_query_planes(spec.config, shards, last)
    return _planes_topk(spec.config, planes, kind, k, direction)


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("kind", "k", "direction"))
def _topk_pallas(spec, planes, *, kind, k, direction):
    _count("hh_" + kind, "pallas")
    return _planes_topk(spec.config, planes, kind, k, direction)


@functools.partial(jax.jit, static_argnums=(0, 1),
                   static_argnames=("kind", "k", "direction"))
def _topk_collective(spec, ctx, planes, *, kind, k, direction):
    _count("hh_" + kind, "collective")

    def body(planes):
        return _planes_topk(spec.config, planes, kind, k, direction,
                            axis_name=ctx.axis)

    return _shmap(body, ctx, 0)(planes)


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("kind", "k", "direction"))
def _topk_pallas_multi(spec, planes, *, kind, k, direction):
    """Horizon-sweep top-k over a stacked ``MultiPlanes``: the per-horizon
    decodes unroll inside ONE jitted program (the decode kernel is not
    vmapped — unrolling keeps the pallas call shapes identical to the
    single-horizon path), returning ``[H, k]``-stacked rankings."""
    _count("hh_" + kind, "pallas-multi")
    H = planes.cw.shape[0]
    outs = [_planes_topk(spec.config, _cq.slice_horizon(planes, i), kind, k,
                         direction) for i in range(H)]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *outs)


@functools.partial(jax.jit, static_argnums=(0, 1),
                   static_argnames=("kind", "k", "direction"))
def _topk_collective_multi(spec, ctx, planes, *, kind, k, direction):
    _count("hh_" + kind, "collective-multi")

    def body(planes):
        H = planes.cw.shape[0]
        outs = [_planes_topk(spec.config, _cq.slice_horizon(planes, i), kind,
                             k, direction, axis_name=ctx.axis)
                for i in range(H)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *outs)

    return _shmap_multi(body, ctx, 0)(planes)


def _analytics(spec: SketchSpec, state, kind: str, k: int, direction: str,
               last, path: str, horizons=None):
    if spec.kind == "lgs":
        raise NotImplementedError(
            "LGS cells store no keys — the reversible cell-owner decode "
            "needs LSketch/GSS")
    if horizons is not None and last is not None:
        raise ValueError("pass either last= (one horizon) or horizons= "
                         "(a sweep), not both")
    if spec.kind == "gss":
        if horizons is not None:  # no window ring: one ranking fits all
            out = _analytics(spec, state, kind, k, direction, None, path)
            return jax.tree.map(
                lambda x: jnp.broadcast_to(x[None],
                                           (len(horizons),) + x.shape), out)
        last = None  # no window ring to restrict
    path = resolve_query_path(spec, path)
    stacked = isinstance(state, ShardedState)
    shards = state.shards if stacked else state
    ctx = mesh_context(state) if stacked else None
    if path == "pallas" and ctx is not None and ctx.divides(spec.n_shards):
        # a mesh-placed handle decodes each shard block on its own device
        # (the collective body, bit-identical): GSPMD cannot partition
        # the decode kernel over mesh-sharded planes
        path = "collective"
    if horizons is not None:
        horizons = list(horizons)
        if not horizons:
            raise ValueError("horizons= needs at least one horizon")
        if path == "scan":
            outs = [_analytics(spec, state, kind, k, direction,
                               None if h is None else int(h), path)
                    for h in horizons]
            return jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        _, sel = _normalize_horizons(spec, horizons)
        collective = path == "collective"
        planes, _ = query_planes_multi(spec, state, horizons,
                                       collective=collective)
        if collective:
            ctx = _collective_ctx(spec, state)
            out = _topk_collective_multi(spec, ctx, planes, kind=kind, k=k,
                                         direction=direction)
        else:
            out = _topk_pallas_multi(spec, planes, kind=kind, k=k,
                                     direction=direction)
        sel_arr = jnp.asarray(sel, jnp.int32)
        return jax.tree.map(lambda x: x[sel_arr], out)
    if path == "collective":
        ctx = _collective_ctx(spec, state)
        planes = query_planes(spec, state, last, collective=True)
        return _topk_collective(spec, ctx, planes, kind=kind, k=k,
                                direction=direction)
    if path == "pallas":
        planes = query_planes(spec, state, last)
        return _topk_pallas(spec, planes, kind=kind, k=k,
                            direction=direction)
    return _topk_sharded(spec, shards, kind=kind, k=k, direction=direction,
                         last=last, stacked=stacked)


def heavy_vertices(spec: SketchSpec, state, k: int = 10, *,
                   direction: str = "out", last=None, horizons=None,
                   path: str = "auto"):
    """Top-k vertices by windowed out/in weight across all shards.

    Returns (vids [k] int32, weights [k] int32): packed (block, address,
    fingerprint) identities recovered by key reversibility, descending
    weight, ties ascending vid, (-1, 0) padding. One-sided (over-)
    estimates, same guarantee as ``edge_weight``.

    ``horizons=[h1, ..., hH]`` (exclusive with ``last=``) sweeps the
    ranking across time horizons in one dispatch — ``([H, k], [H, k])``
    out, row ``i`` bit-identical to ``last=horizons[i]`` — served from
    one horizon-stacked plane build (DESIGN.md §14).
    """
    return _analytics(spec, state, "vertex", k, direction, last, path,
                      horizons=horizons)


def heavy_edges(spec: SketchSpec, state, k: int = 10, *, last=None,
                horizons=None, path: str = "auto"):
    """Top-k edges by windowed weight: (src [k], dst [k], weights [k]).

    Matrix cells and overflow-pool entries rank together (an edge that
    overflowed to the pool keeps its full weight); ties break by
    ascending (src_vid, dst_vid). ``horizons=`` sweeps as in
    ``heavy_vertices`` (``[H, k]`` rows).
    """
    return _analytics(spec, state, "edge", k, "out", last, path,
                      horizons=horizons)


def top_labels(spec: SketchSpec, state, k: int = 10, *,
               direction: str = "out", last=None, horizons=None,
               path: str = "auto"):
    """Top-k vertex-label blocks by windowed out/in weight:
    (blocks [k], weights [k]) — the decoded vid's block id is its label.
    ``horizons=`` sweeps as in ``heavy_vertices`` (``[H, k]`` rows)."""
    return _analytics(spec, state, "label", k, direction, last, path,
                      horizons=horizons)


# --------------------------------------------------------------------------
# batched reachability
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("stacked", "last"))
def _exists_batched(spec, shards, pairs, *, stacked=True, last=None):
    shards = _with_group_window(_lift(shards, stacked))
    hit = jax.vmap(lambda st: _cq._edge_exists_by_vid(
        spec.config, st, pairs, last))(shards)
    return jnp.any(hit, axis=0)


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("stacked", "last"))
def _succ_batched(spec, shards, vids, *, stacked=True, last=None):
    shards = _with_group_window(_lift(shards, stacked))
    return jax.vmap(lambda st: _cq._successors_by_vid(
        spec.config, st, vids, last))(shards)


def _bucket_i32(xs, fill):
    n = max(1, len(xs))
    to = 1 << (n - 1).bit_length()
    return jnp.asarray(np.pad(np.asarray(xs, np.int32), (0, to - len(xs)),
                              constant_values=fill))


def reachable_many(spec: SketchSpec, state, src, src_label, dst, dst_label,
                   *, max_hops: int = 8, last=None,
                   horizons=None) -> np.ndarray:
    """Batched multi-hop reachability: bool [B], True where a path of 1..
    ``max_hops`` edges connects (src, src_label) to (dst, dst_label).

    Host frontier loop shared across the whole batch: per hop, ONE batched
    direct-edge check over every (frontier vertex, target) pair and ONE
    successor scan over the *union* of active frontiers (each unique
    vertex expanded once, however many queries share it) — the batched
    form of ``core.queries.path_reachability``, unioned across shards.

    ``last=h`` restricts every edge check to the h most recent windows.
    ``horizons=[h1, ..., hH]`` (exclusive with ``last=``) sweeps that
    restriction and returns bool ``[H, B]``, row ``i`` identical to
    ``last=horizons[i]``. Validity masks nest (DESIGN.md §14), so
    reachable(h) ⊆ reachable(h') for h ≤ h': the sweep evaluates the
    loosest horizon on the full batch, then re-walks only the
    still-reachable pairs at each tighter horizon.
    """
    if spec.kind == "lgs":
        raise NotImplementedError(
            "LGS cells store no keys — successor recovery needs LSketch/GSS")
    if horizons is not None and last is not None:
        raise ValueError("pass either last= (one horizon) or horizons= "
                         "(a sweep), not both")
    if spec.kind == "gss":
        last = None  # no window ring to restrict
        if horizons is not None:
            out = reachable_many(spec, state, src, src_label, dst, dst_label,
                                 max_hops=max_hops)
            return np.broadcast_to(out[None],
                                   (len(horizons),) + out.shape).copy()
    if horizons is not None:
        horizons = list(horizons)
        if not horizons:
            raise ValueError("horizons= needs at least one horizon")
        k = spec.config.effective_k
        clamp = [k if h is None else min(int(h), k) for h in horizons]
        src_b = np.atleast_1d(np.asarray(src, np.int64))
        B = src_b.shape[0]
        sl_b = np.broadcast_to(np.asarray(src_label, np.int64), (B,))
        dst_b = np.broadcast_to(np.asarray(dst, np.int64), (B,))
        dl_b = np.broadcast_to(np.asarray(dst_label, np.int64), (B,))
        by_h: dict[int, np.ndarray] = {}
        alive: np.ndarray | None = None  # still reachable at looser horizon
        for h in sorted(set(clamp), reverse=True):
            if alive is None:  # loosest horizon: full batch
                by_h[h] = np.asarray(reachable_many(
                    spec, state, src_b, sl_b, dst_b, dl_b,
                    max_hops=max_hops, last=h), bool)
            else:
                row = np.zeros(B, bool)
                if alive.size:
                    row[alive] = np.asarray(reachable_many(
                        spec, state, src_b[alive], sl_b[alive], dst_b[alive],
                        dl_b[alive], max_hops=max_hops, last=h), bool)
                by_h[h] = row
            alive = np.nonzero(by_h[h])[0]
        return np.stack([by_h[h] for h in clamp])
    if last is not None:
        last = min(int(last), spec.config.effective_k)
    cfg = spec.config
    stacked = isinstance(state, ShardedState)
    shards = state.shards if stacked else state
    src = np.atleast_1d(np.asarray(src))
    B = src.shape[0]
    pre_s = precompute(cfg, jnp.asarray(src, jnp.int32),
                       jnp.asarray(np.broadcast_to(src_label, (B,)),
                                   jnp.int32))
    pre_d = precompute(cfg, jnp.asarray(np.broadcast_to(dst, (B,)),
                                        jnp.int32),
                       jnp.asarray(np.broadcast_to(dst_label, (B,)),
                                   jnp.int32))
    targets = np.asarray(pre_d.vid)
    frontiers = [{int(v)} for v in np.asarray(pre_s.vid)]
    visited = [set(f) for f in frontiers]
    done = np.zeros(B, bool)
    for _ in range(max_hops):
        active = [i for i in range(B) if not done[i] and frontiers[i]]
        if not active:
            break
        # one batched direct-edge check for every (frontier, target) pair
        owners = [i for i in active for _ in frontiers[i]]
        fr = [v for i in active for v in frontiers[i]]
        pairs = jnp.stack([_bucket_i32(fr, -1),
                           _bucket_i32([int(targets[i]) for i in owners],
                                       -2)], axis=1)
        hit = np.asarray(_exists_batched(spec, shards, pairs, stacked=stacked,
                                         last=last))[:len(fr)]
        for j, i in enumerate(owners):
            if hit[j]:
                done[i] = True
        # one successor scan over the union of still-active frontiers
        uniq = sorted({v for i in active if not done[i] for v in frontiers[i]})
        if not uniq:
            continue
        succ, valid = _succ_batched(spec, shards, _bucket_i32(uniq, -1),
                                    stacked=stacked, last=last)
        succ = np.asarray(succ)   # [S, U', L]
        valid = np.asarray(valid)
        succ_of = {}
        for u, v in enumerate(uniq):
            s = succ[:, u][valid[:, u]]
            succ_of[v] = set(np.unique(s[s >= 0]).tolist())
        for i in active:
            if done[i]:
                continue
            nf = set()
            for v in frontiers[i]:
                nf |= succ_of[v]
            frontiers[i] = nf - visited[i]
            visited[i] |= nf
    return done
