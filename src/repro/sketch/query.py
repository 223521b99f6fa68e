"""Batched queries over a sharded handle (DESIGN.md §6/§8).

``query(spec, state, QueryBatch, path=...)`` fans one array-shaped query
batch through every shard and sums the shard contributions in a single
jitted dispatch: hash partitioning makes shard estimates disjoint (each
logical edge lives on exactly one shard), so addition is the exact
combinator for every query kind — edge weights, vertex aggregates, and
label aggregates.

Three read paths answer the same queries bit-identically (DESIGN.md §8/§9):

  * ``path="scan"`` — the dense reference: ``core/queries.py`` vmapped
    over shards, re-reducing the ``[d, d, 2, k(, c)]`` counter planes
    under the window mask on every dispatch. The conformance baseline.
  * ``path="pallas"`` — the planes path: queries run against cached
    **window-reduced planes** (``core.queries.QueryPlanes``) through the
    compiled ``sketch_query``/``vertex_scan`` XLA programs (the same
    program on every backend; top-k decodes on the ``heavy_hitters``
    Pallas kernel on TPU).
    The planes are a pure function of ``(state, last)``: they are built
    lazily on the first kernel-path query of a handle and memoized on the
    handle object itself, so a serving loop answering many queries
    between ingest flushes pays the dense reduction once, not per call.
    Every state-producing operation (``ingest``, ``restore``,
    ``merge_all``, the AsyncIngestor's dispatches) returns a *new*
    immutable handle, which is exactly the cache invalidation: stale
    planes cannot be served because the old handle is never queried
    again (regression-tested in tests/test_query_path.py).
  * ``path="collective"`` — the mesh-resident path (DESIGN.md §9): for a
    handle carrying a ``MeshContext`` (``place`` attaches it), the same
    plane walk runs inside ``jax.shard_map`` over the shard axis, each
    device answering against its local shard block of a **device-resident
    plane cache** (planes built under the state's own sharding, memoized
    with the identical handle-identity contract), and the per-shard
    partials reduce with ``lax.psum`` (``core.merge.psum_partials``) —
    the query never funnels shard partials through the host. Bit-identical
    to the other paths: int32 addition is associative, so the two-level
    (local, cross-device) reduce equals the host-side ``sum(axis=0)``.

``path="auto"`` mirrors the ingest rule: pallas on TPU, scan elsewhere.
LGS always takes scan (count-min cells — no keyed walk, no planes).

Window reconciliation: a shard that saw no recent items still carries the
ring bookkeeping of the last item it *did* see, so each shard's
``cur_widx`` is first replaced by the global (max) one — otherwise a
lagging shard would count ring slots the combined stream already expired.
The plane builder applies the same reconciliation before reducing.

Padding: query batches are padded to power-of-two buckets so a serving
loop compiles O(log max_batch) shapes. Pad rows are filled with the
``EMPTY`` sentinel (-1) rather than vertex id 0 — a real id — so a pad row
can never alias a live vertex's cell probes; answers for pad rows are
sliced off before returning either way (regression-tested).
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import queries as _q
from repro.core.lgs import _lgs_edge_query, _lgs_vertex_query
from repro.core.types import EMPTY
from repro.engine.window import bucket_size
from repro.kernels import use_tpu_kernels

from .spec import SketchSpec
from .state import ShardedState, mesh_context
from . import trace

# trace-time counters keyed by (kind, path) — tests assert one jitted
# program per (kind, bucket, path) by reading these before/after a
# workload; ("planes", "build")/("planes", "delta") count plane-builder /
# delta-apply traces (the "-multi" variants are the horizon-stacked
# programs, DESIGN.md §14); PLANES_BUILD_COUNTS counts host-side cache
# misses: "build" full rebuilds, "delta" misses resolved by folding
# pending flush deltas into the parent handle's planes (DESIGN.md §10),
# "evict" LRU drops from a handle's plane cache.
QUERY_TRACE_COUNTS: dict = {}
PLANES_BUILD_COUNTS = {"build": 0, "delta": 0, "evict": 0}

_PLANES_ATTR = "_query_planes_cache"
_PENDING_ATTR = "_planes_pending"

# Per-handle plane-cache entry cap (LRU). A horizon-sweep workload would
# otherwise accumulate one entry per distinct (family, horizon) key for the
# life of the handle; a stacked MultiPlanes answers a whole sweep as ONE
# entry, so a small cap never thrashes a realistic serving mix.
PLANES_CACHE_CAP = 8

# Longest delta chain a handle will resolve before falling back to a full
# rebuild: N un-queried flushes cost N sequential applies at the next
# query, and past a few links one fused rebuild is both cheaper and frees
# the chain's buffers. 8 covers any realistic serving cadence.
MAX_DELTA_CHAIN = 8


def _count(kind: str, path: str) -> None:
    QUERY_TRACE_COUNTS[(kind, path)] = QUERY_TRACE_COUNTS.get(
        (kind, path), 0) + 1


@dataclass(frozen=True)
class QueryBatch:
    """One homogeneous batch of queries (single kind / window / direction —
    the static axes of the underlying jitted query programs).

    ``last`` is either one horizon (``int | None``, the classic
    time-sensitive restriction) or a list/tuple of horizons — a
    multi-horizon sweep answered as ``[H, B]`` from one horizon-stacked
    plane build (DESIGN.md §14), rows in the order the horizons were
    given."""

    kind: str  # "edge" | "vertex" | "label"
    src: Any = None
    src_label: Any = None
    dst: Any = None
    dst_label: Any = None
    vertex: Any = None
    vertex_label: Any = None
    edge_label: Any = None
    direction: str = "out"
    last: Any = None  # int | None | sequence of (int | None)

    @classmethod
    def edges(cls, src, src_label, dst, dst_label, edge_label=None,
              last=None) -> "QueryBatch":
        return cls(kind="edge", src=src, src_label=src_label, dst=dst,
                   dst_label=dst_label, edge_label=edge_label, last=last)

    @classmethod
    def vertices(cls, vertex, vertex_label, edge_label=None,
                 direction: str = "out", last=None) -> "QueryBatch":
        return cls(kind="vertex", vertex=vertex, vertex_label=vertex_label,
                   edge_label=edge_label, direction=direction, last=last)

    @classmethod
    def labels(cls, vertex_label, edge_label=None, direction: str = "out",
               last=None) -> "QueryBatch":
        return cls(kind="label", vertex_label=vertex_label,
                   edge_label=edge_label, direction=direction, last=last)


# --------------------------------------------------------------------------
# path selection (mirrors engine.insert.resolve_path)
# --------------------------------------------------------------------------

def default_query_path() -> str:
    """The planes path is the default on TPU; the dense vmapped scan is
    the reference/CPU default (same rule as ingest)."""
    return "pallas" if use_tpu_kernels() else "scan"


def resolve_query_path(spec: SketchSpec, path: str = "auto") -> str:
    """Normalize a user-facing query path name to
    "scan" | "pallas" | "collective".

    "auto" is the backend default; LGS silently takes "scan" (count-min
    cells store no keys — there is no probe walk or plane reduction to
    kernelize, on any path). Unlike ingest, skewed blocking needs no
    fallback: the query kernels address absolute rows/cols, not uniform
    tiles. "collective" additionally requires a mesh-resident handle —
    validated at dispatch (``query``), where the state is in hand.
    """
    if path == "auto":
        path = default_query_path()
    if path in ("pallas", "collective") and spec.kind == "lgs":
        path = "scan"
    if path not in ("scan", "pallas", "collective"):
        raise ValueError(f"unknown query path {path!r}")
    return path


def _collective_ctx(spec: SketchSpec, state):
    """Validate and fetch the MeshContext a collective query runs under."""
    ctx = mesh_context(state) if isinstance(state, ShardedState) else None
    if ctx is None:
        raise ValueError(
            "path='collective' needs a mesh-resident handle: lay the shard "
            "axis over a mesh axis with repro.sketch.place(...) (or attach "
            "an existing layout with with_mesh(...)) first")
    if not ctx.divides(spec.n_shards):
        raise ValueError(
            f"path='collective' needs the mesh axis to divide the shard "
            f"count (shard_map blocks must be uniform): n_shards="
            f"{spec.n_shards} over {ctx.n_devices} devices on axis "
            f"{ctx.axis!r} is replicated, not sharded — use the host "
            "fan-out paths (scan/pallas) or repartition")
    return ctx


# --------------------------------------------------------------------------
# array normalization + bucket padding (shared with engine.query_batch)
# --------------------------------------------------------------------------

def as_i32(x, n: int | None = None) -> jnp.ndarray:
    """int32 1-D array, broadcast to length ``n`` (scalar labels with array
    vertices is the common serving shape)."""
    a = jnp.atleast_1d(jnp.asarray(x, jnp.int32))
    if n is not None and a.shape[0] != n:
        a = jnp.broadcast_to(a, (n,))
    return a


def pad_all(n: int, *arrays, floor: int = 32):
    """Pad every [n] array to the common bucket size with the ``EMPTY``
    sentinel — pad rows address no real vertex/label, and their answers
    are sliced off by the caller."""
    to = bucket_size(n, floor=floor)
    if to == n:
        return arrays
    return tuple(
        jnp.concatenate([a, jnp.full((to - a.shape[0],), EMPTY, a.dtype)])
        for a in arrays)


def normalize_query(spec: SketchSpec, q: QueryBatch):
    """Shared query-frontend normalization: int32 arrays, broadcast,
    GSS degeneration (labels/window normalized away), bucket padding with
    the ``EMPTY`` sentinel. Returns ``(arrays, with_le, last, n)`` where
    ``arrays`` is the padded per-kind tuple — ``(src, dst, la, lb, les)``
    for edges, ``(v, lv, les)`` for vertices, ``(lv, les)`` for labels —
    and ``n`` the unpadded row count (slice answers to ``[:n]``). Used by
    both ``query`` here and the pooled multi-tenant frontend
    (``repro.sketch.tenant``), so every dispatch path pads identically.
    """
    if q.kind == "edge":
        src, dst = as_i32(q.src), as_i32(q.dst)
        n = max(src.shape[0], dst.shape[0])
        src, dst = as_i32(src, n), as_i32(dst, n)
        la, lb = as_i32(q.src_label, n), as_i32(q.dst_label, n)
        le, last = q.edge_label, q.last
        if spec.kind == "gss":  # degenerate: no labels, no window
            la, lb, le, last = (jnp.zeros_like(la), jnp.zeros_like(lb),
                                None, None)
        with_le = le is not None
        les = as_i32(le, n) if with_le else jnp.zeros_like(src)
        return pad_all(n, src, dst, la, lb, les), with_le, last, n
    if q.kind == "vertex":
        v = as_i32(q.vertex)
        n = v.shape[0]
        lv = as_i32(q.vertex_label, n)
        le, last = q.edge_label, q.last
        if spec.kind == "gss":
            lv, le, last = jnp.zeros_like(lv), None, None
        with_le = le is not None
        les = as_i32(le, n) if with_le else jnp.zeros_like(v)
        return pad_all(n, v, lv, les), with_le, last, n
    if q.kind == "label":
        if spec.kind == "lgs":
            raise NotImplementedError(
                "LGS stores no label blocks; label aggregates need "
                "LSketch/GSS")
        lv = as_i32(q.vertex_label)
        n = lv.shape[0]
        le, last = q.edge_label, q.last
        if spec.kind == "gss":
            lv, le, last = jnp.zeros_like(lv), None, None
        with_le = le is not None
        les = as_i32(le, n) if with_le else jnp.zeros_like(lv)
        return pad_all(n, lv, les), with_le, last, n
    raise ValueError(f"unknown query kind {q.kind!r}")


def _with_group_window(shards, groups: int = 1):
    """Every shard queries under its window group's newest subwindow index.

    One group (the default) is the plain sharded handle: the whole fleet
    reconciles to one global ``cur_widx``. A pooled multi-tenant handle
    (``repro.sketch.tenant``, DESIGN.md §11) stacks ``groups`` tenants'
    shard blocks on the leading axis — tenant timelines are independent,
    so each tenant's block reconciles only within itself (the max lifts
    over axis 1 of the ``[groups, S//groups]`` view), exactly matching
    what ``groups`` independent handles would each compute.
    """
    cw = shards.cur_widx
    S = cw.shape[0]
    per = S // groups
    gm = jnp.max(cw.reshape((groups, per) + cw.shape[1:]), axis=1,
                 keepdims=True)
    g = jnp.broadcast_to(gm, (groups, per) + cw.shape[1:]).reshape(cw.shape)
    return dataclasses.replace(shards, cur_widx=g)


def _with_global_window(shards):
    """Every shard queries under the fleet-wide newest subwindow index."""
    return _with_group_window(shards, 1)


def _lift(shards, stacked: bool):
    """Inside-jit lift of a plain (unstacked) state to a 1-shard stack —
    XLA aliases the reshape, so the object-API path (which passes its state
    un-lifted) never pays an eager whole-state copy per query."""
    if stacked:
        return shards
    return jax.tree.map(lambda x: x[None], shards)


# --------------------------------------------------------------------------
# window-plane cache (the "pallas" path's reduction memo)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("horizon", "stacked", "groups"))
def _build_planes(spec, shards, *, horizon, stacked=True, groups=1):
    _count("planes", "build")
    shards = _with_group_window(_lift(shards, stacked), groups)
    return _q.build_query_planes(spec.config, shards, horizon)


@functools.partial(jax.jit, static_argnums=(0, 1, 2),
                   static_argnames=("horizon",))
def _build_planes_collective(spec, mesh, axis, shards, *, horizon):
    """Device-resident plane build: each device reduces only its local
    shard block, under the same global-``cur_widx`` reconciliation (the
    max-lift becomes a ``pmax`` across the mesh axis). The output planes
    carry the state's own sharding (leading shard axis over ``axis``), so
    the collective query dispatches consume them with zero re-layout.
    """
    _count("planes", "build")

    def body(sh):
        g = jax.lax.pmax(jnp.max(sh.cur_widx, axis=0), axis)
        sh = dataclasses.replace(
            sh, cur_widx=jnp.broadcast_to(g, sh.cur_widx.shape))
        return _q.build_query_planes(spec.config, sh, horizon)

    # check_vma=False: the bodies mix shard-varying and replicated values
    # through gathers/scatters; correctness is pinned by the scan parity
    # tests, not the varying-manual-axes checker
    return jax.shard_map(body, mesh=mesh, in_specs=P(axis),
                         out_specs=P(axis), check_vma=False)(shards)


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("horizon", "groups"))
def _apply_planes_delta(spec, shards, planes, delta, *, horizon, groups=1):
    """Fold one flush's ``PlanesDelta`` into cached host planes — the warm
    path of an ingest-flush cache miss. Same ``cur_widx`` reconciliation
    as ``_build_planes`` — global for a plain handle, per tenant group for
    a pooled one (unchanged by construction when ``delta.ok`` held on the
    coupled rows, so the masks match the cached planes')."""
    _count("planes", "delta")
    shards = _with_group_window(shards, groups)
    return _q.apply_planes_delta(spec.config, shards, planes, delta, horizon)


@functools.partial(jax.jit, static_argnums=(0, 1, 2),
                   static_argnames=("horizon",))
def _apply_planes_delta_collective(spec, mesh, axis, shards, planes, delta,
                                   *, horizon):
    """Device-resident delta apply: each device folds its local shard
    block's increment into its local plane block — mesh planes survive a
    flush without a device-wide rebuild. Every delta leaf — ``ok`` is
    per shard row like ``slot`` — shards on the mesh axis with the
    planes."""
    _count("planes", "delta")

    def body(sh, pl, dl):
        g = jax.lax.pmax(jnp.max(sh.cur_widx, axis=0), axis)
        sh = dataclasses.replace(
            sh, cur_widx=jnp.broadcast_to(g, sh.cur_widx.shape))
        return _q.apply_planes_delta(spec.config, sh, pl, dl, horizon)

    dspec = _q.PlanesDelta(ok=P(axis), slot=P(axis), d_c=P(axis), d_p=P(axis),
                           d_pool_c=P(axis), d_pool_p=P(axis))
    return jax.shard_map(body, mesh=mesh, in_specs=(P(axis), P(axis), dspec),
                         out_specs=P(axis), check_vma=False)(shards, planes,
                                                             delta)


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("horizons", "stacked", "groups"))
def _build_planes_multi(spec, shards, *, horizons, stacked=True, groups=1):
    """Horizon-stacked plane build (DESIGN.md §14): ONE pass over the ring
    emits every horizon's planes — O(k + H) instead of H single builds'
    O(H·k). Same window reconciliation as ``_build_planes``."""
    _count("planes", "build-multi")
    shards = _with_group_window(_lift(shards, stacked), groups)
    return _q.build_query_planes_multi(spec.config, shards, horizons)


def _multi_pspecs(axis):
    """PartitionSpecs of a mesh-resident MultiPlanes: the horizon axis is
    replicated (every device serves every horizon), the shard axis — now
    second — lays over the mesh axis exactly like single planes."""
    s = P(None, axis)
    return _q.MultiPlanes(key=s, cw=s, pw=s, pool_key=s, pool_cw=s,
                          pool_pw=s)


@functools.partial(jax.jit, static_argnums=(0, 1, 2),
                   static_argnames=("horizons",))
def _build_planes_collective_multi(spec, mesh, axis, shards, *, horizons):
    """Device-resident horizon-stacked build: each device bands only its
    local shard block under the pmax-globalized window; the output keeps
    the state's shard layout on axis 1 with the horizon axis replicated."""
    _count("planes", "build-multi")

    def body(sh):
        g = jax.lax.pmax(jnp.max(sh.cur_widx, axis=0), axis)
        sh = dataclasses.replace(
            sh, cur_widx=jnp.broadcast_to(g, sh.cur_widx.shape))
        return _q.build_query_planes_multi(spec.config, sh, horizons)

    return jax.shard_map(body, mesh=mesh, in_specs=P(axis),
                         out_specs=_multi_pspecs(axis),
                         check_vma=False)(shards)


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("horizons", "groups"))
def _apply_planes_delta_multi(spec, shards, planes, delta, *, horizons,
                              groups=1):
    """Fold one flush's ``PlanesDelta`` into ALL cached horizons in one
    dispatch — the reason a horizon-sweep serving loop's per-flush cost is
    O(1) in H rather than H single applies."""
    _count("planes", "delta-multi")
    shards = _with_group_window(shards, groups)
    return _q.apply_planes_delta_multi(spec.config, shards, planes, delta,
                                       horizons)


@functools.partial(jax.jit, static_argnums=(0, 1, 2),
                   static_argnames=("horizons",))
def _apply_planes_delta_collective_multi(spec, mesh, axis, shards, planes,
                                         delta, *, horizons):
    _count("planes", "delta-multi")

    def body(sh, pl, dl):
        g = jax.lax.pmax(jnp.max(sh.cur_widx, axis=0), axis)
        sh = dataclasses.replace(
            sh, cur_widx=jnp.broadcast_to(g, sh.cur_widx.shape))
        return _q.apply_planes_delta_multi(spec.config, sh, pl, dl, horizons)

    dspec = _q.PlanesDelta(ok=P(axis), slot=P(axis), d_c=P(axis), d_p=P(axis),
                           d_pool_c=P(axis), d_pool_p=P(axis))
    mspec = _multi_pspecs(axis)
    return jax.shard_map(body, mesh=mesh, in_specs=(P(axis), mspec, dspec),
                         out_specs=mspec, check_vma=False)(shards, planes,
                                                           delta)


def planes_delta_base(state):
    """The ``(base planes dict, prior delta chain)`` the next ingest flush
    should extend, or None when the handle carries nothing a delta could
    keep warm (then the flush skips delta emission entirely — a pure-ingest
    workload pays zero overhead). Called by ``repro.sketch.ingest`` on the
    handle it is about to consume."""
    cache = getattr(state, _PLANES_ATTR, None)
    if cache:
        # resolved planes on this handle: one fresh link suffices
        return dict(cache), []
    pend = getattr(state, _PENDING_ATTR, None)
    if pend is not None and len(pend[1]) < MAX_DELTA_CHAIN:
        return pend
    return None


def attach_planes_delta(state, base: dict, chain: list, delta) -> None:
    """Hang a pending ``(base planes, delta chain + [delta])`` off a fresh
    ingest handle — same host-attribute idiom as the plane cache itself
    (never traverses jit/donation; resolved lazily by ``query_planes``)."""
    object.__setattr__(state, _PENDING_ATTR, (base, chain + [delta]))


def _resolve_pending(state, ckey, apply_one):
    """Try to serve a plane-cache miss by folding the handle's pending
    flush deltas into the parent's cached planes. Returns the planes, or
    None when incrementality does not hold (any link's flush reset a ring
    slot / advanced the window / spanned several subwindows on any shard
    row — the ring moved, so the chain is useless for *every* horizon and
    is dropped) or the parent never cached this entry. ``apply_one`` is
    the right jitted fold for the entry family — single vs horizon-stacked,
    host vs collective, global vs per-group window lift.

    ``delta.ok`` is per shard row; the chain applies only when every row
    of every link held (all rows' rings unchanged => every group's
    reconciled mask unchanged). A pooled handle whose groups moved
    independently could in principle delta-apply the untouched groups and
    rebuild only the moved ones, but a partial rebuild costs the same full
    counter reduction — so a single bad row drops the whole chain."""
    pend = getattr(state, _PENDING_ATTR, None)
    if pend is None:
        return None
    base, deltas = pend
    if ckey not in base:
        return None
    for d in deltas:
        # one device read per link, paid on the first query of the handle
        # (which was about to block on the flush results anyway)
        with trace.span("planes.wait"):
            ok = bool(jnp.all(d.ok))
        if not ok:
            object.__setattr__(state, _PENDING_ATTR, None)
            return None
    planes = base[ckey]
    # all links ok => the ring never moved across the chain, so every
    # link's mask equals the final state's — apply them all under it
    with trace.span("planes.fold"):
        for d in deltas:
            planes = apply_one(planes, d)
    PLANES_BUILD_COUNTS["delta"] += 1
    return planes


def _cache_touch(cache: dict, ckey):
    """Refresh LRU recency of a hit (dict insertion order is the LRU)."""
    cache[ckey] = cache.pop(ckey)
    return cache[ckey]


def _cache_put(cache: dict, ckey, planes):
    """Insert as most-recent; evict the least-recent past the cap. A
    stacked MultiPlanes is one entry like any other."""
    cache.pop(ckey, None)
    while len(cache) >= PLANES_CACHE_CAP:
        cache.pop(next(iter(cache)))
        PLANES_BUILD_COUNTS["evict"] += 1
    cache[ckey] = planes


def _multi_horizons_of(ckey, mkey):
    """The horizon tuple of multi entry ``mkey`` iff it is the stacked
    family of single-horizon key ``ckey``, else None. Families pair
    ``horizon``/("multi", hs), ("collective", h)/("multi-collective", hs),
    ("pooled", g, h)/("multi-pooled", g, hs)."""
    if not isinstance(mkey, tuple):
        return None
    if isinstance(ckey, int):
        return mkey[1] if mkey[0] == "multi" else None
    if ckey[0] == "collective":
        return mkey[1] if mkey[0] == "multi-collective" else None
    if ckey[0] == "pooled":
        if mkey[0] == "multi-pooled" and mkey[1] == ckey[1]:
            return mkey[2]
    return None


def _multi_slice_hit(cache: dict, ckey, horizon):
    """Serve a single-horizon miss from a same-family stacked entry that
    covers the horizon: one device-side slice of the MultiPlanes row — no
    rebuild, no delta walk, neither counter moves. Most-recent stacked
    entry wins; the hit refreshes its recency."""
    for mkey in reversed(list(cache)):
        hs = _multi_horizons_of(ckey, mkey)
        if hs is not None and horizon in hs:
            planes = _cache_touch(cache, mkey)
            return _q.slice_horizon(planes, hs.index(horizon))
    return None


def query_planes(spec: SketchSpec, state, last=None, *,
                 collective: bool = False, groups: int = 1):
    """The window-reduced ``QueryPlanes`` for ``(state, last)``, memoized
    on the state object (handles are immutable — every ingest/restore/
    merge returns a new one, so a hit is always exact). Horizons that
    alias the same validity mask (``last=None`` vs ``last>=k``) share one
    entry; the cache is a small LRU (``PLANES_CACHE_CAP``). A miss first
    checks whether a same-family horizon-stacked entry
    (``query_planes_multi``) covers the horizon — then the answer is one
    slice of the stacked build, not a rebuild — then tries the incremental
    path — folding the flush's ``PlanesDelta`` chain into the parent
    handle's cached planes (DESIGN.md §10) — and rebuilds from the full
    counters only when the flush moved the ring or the parent had nothing
    cached for this horizon. With ``collective=True`` the planes are built
    and kept under the handle's mesh sharding (one device-resident entry
    per horizon, same identity contract — the cache key just gains the
    layout; the delta path applies device-locally via ``shard_map``).
    With ``groups > 1`` (a pooled multi-tenant handle, DESIGN.md §11) the
    window reconciliation lifts per tenant group instead of globally, and
    the entry is keyed apart from the global-lift planes. Public so
    serving loops can pre-warm the cache after a flush.
    """
    if collective and groups != 1:
        raise ValueError("pooled (grouped) planes are host-resident: "
                         "collective=True requires groups=1")
    k = spec.config.effective_k
    horizon = k if last is None else min(int(last), k)
    cache = getattr(state, _PLANES_ATTR, None)
    if cache is None:
        cache = {}
        object.__setattr__(state, _PLANES_ATTR, cache)
    if collective:
        ckey = ("collective", horizon)
    elif groups != 1:
        ckey = ("pooled", groups, horizon)
    else:
        ckey = horizon
    if ckey in cache:
        return _cache_touch(cache, ckey)
    planes = _multi_slice_hit(cache, ckey, horizon)
    if planes is None:
        if collective:
            ctx = _collective_ctx(spec, state)

            def apply_one(pl, d):
                return _apply_planes_delta_collective(
                    spec, ctx.mesh, ctx.axis, state.shards, pl, d,
                    horizon=horizon)
        else:
            def apply_one(pl, d):
                return _apply_planes_delta(spec, state.shards, pl, d,
                                           horizon=horizon, groups=groups)
        planes = _resolve_pending(state, ckey, apply_one)
    if planes is None:
        PLANES_BUILD_COUNTS["build"] += 1
        with trace.span("planes.build"):
            if collective:
                ctx = _collective_ctx(spec, state)
                planes = _build_planes_collective(
                    spec, ctx.mesh, ctx.axis, state.shards, horizon=horizon)
            else:
                stacked = isinstance(state, ShardedState)
                shards = state.shards if stacked else state
                planes = _build_planes(spec, shards, horizon=horizon,
                                       stacked=stacked, groups=groups)
    _cache_put(cache, ckey, planes)
    return planes


def _normalize_horizons(spec: SketchSpec, lasts):
    """Canonicalize a horizon sweep: each entry clamps exactly like a
    single-horizon query (``None -> k``, ``min(int(h), k)``), the stacked
    build runs over the sorted unique tuple (the static key of the jitted
    multi programs), and ``sel`` maps each user position to its row of the
    stacked output. Returns ``(uniq, sel)``."""
    k = spec.config.effective_k
    hs = [k if h is None else min(int(h), k) for h in lasts]
    uniq = tuple(sorted(set(hs)))
    return uniq, [uniq.index(h) for h in hs]


def query_planes_multi(spec: SketchSpec, state, lasts, *,
                       collective: bool = False, groups: int = 1):
    """The horizon-stacked ``MultiPlanes`` covering every horizon in
    ``lasts`` — ONE pass over the ring (DESIGN.md §14), memoized on the
    state object as a single cache entry, one flush delta folding into all
    horizons in one dispatch on the incremental path. Returns
    ``(planes, uniq)`` where ``uniq`` is the sorted unique clamped horizon
    tuple the rows follow (``_normalize_horizons``); per-horizon lookups
    (``query_planes``) slice into this entry instead of rebuilding.
    Collective/pooled variants key and shard exactly like their
    single-horizon counterparts (horizon axis replicated on the mesh).
    """
    if collective and groups != 1:
        raise ValueError("pooled (grouped) planes are host-resident: "
                         "collective=True requires groups=1")
    uniq, _ = _normalize_horizons(spec, lasts)
    cache = getattr(state, _PLANES_ATTR, None)
    if cache is None:
        cache = {}
        object.__setattr__(state, _PLANES_ATTR, cache)
    if collective:
        ckey = ("multi-collective", uniq)
    elif groups != 1:
        ckey = ("multi-pooled", groups, uniq)
    else:
        ckey = ("multi", uniq)
    if ckey in cache:
        return _cache_touch(cache, ckey), uniq
    if collective:
        ctx = _collective_ctx(spec, state)

        def apply_one(pl, d):
            return _apply_planes_delta_collective_multi(
                spec, ctx.mesh, ctx.axis, state.shards, pl, d, horizons=uniq)
    else:
        def apply_one(pl, d):
            return _apply_planes_delta_multi(spec, state.shards, pl, d,
                                             horizons=uniq, groups=groups)
    planes = _resolve_pending(state, ckey, apply_one)
    if planes is None:
        PLANES_BUILD_COUNTS["build"] += 1
        with trace.span("planes.build"):
            if collective:
                ctx = _collective_ctx(spec, state)
                planes = _build_planes_collective_multi(
                    spec, ctx.mesh, ctx.axis, state.shards, horizons=uniq)
            else:
                stacked = isinstance(state, ShardedState)
                shards = state.shards if stacked else state
                planes = _build_planes_multi(spec, shards, horizons=uniq,
                                             stacked=stacked, groups=groups)
    _cache_put(cache, ckey, planes)
    return planes, uniq


def clear_plane_cache(state) -> None:
    """Drop any memoized ``QueryPlanes`` — and any pending flush-delta
    chain — from a handle. Never needed for correctness (state-producing
    ops return fresh handles); benchmarks use it to time the cold path,
    and it frees plane memory on a handle that will only be checkpointed."""
    if getattr(state, _PLANES_ATTR, None):
        object.__setattr__(state, _PLANES_ATTR, {})
    if getattr(state, _PENDING_ATTR, None) is not None:
        object.__setattr__(state, _PENDING_ATTR, None)


# --------------------------------------------------------------------------
# jitted sharded dispatches (one per kind x path)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("with_le", "last", "stacked"))
def _edge_sharded(spec, shards, src, dst, la, lb, les, *, with_le, last,
                  stacked=True):
    _count("edge", "scan")
    shards = _with_global_window(_lift(shards, stacked))
    if spec.kind == "lgs":
        per = jax.vmap(lambda st: _lgs_edge_query(
            spec.config.key(), st, src, dst, la, lb, les, with_le, last))(
                shards)
    else:
        def one(st):
            w, wl = _q.edge_query(spec.config, st, src, dst, (la, lb, les),
                                  with_le, last)
            return wl if with_le else w
        per = jax.vmap(one)(shards)
    return jnp.sum(per, axis=0)


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("with_le", "direction", "last", "stacked"))
def _vertex_sharded(spec, shards, v, lv, les, *, with_le, direction, last,
                    stacked=True):
    _count("vertex", "scan")
    shards = _with_global_window(_lift(shards, stacked))
    if spec.kind == "lgs":
        per = jax.vmap(lambda st: _lgs_vertex_query(
            spec.config.key(), st, v, lv, les, with_le, direction, last))(
                shards)
    else:
        def one(st):
            w, wl = _q.vertex_query(spec.config, st, v, (lv, les),
                                    direction=direction,
                                    with_edge_label=with_le, last=last)
            return wl if with_le else w
        per = jax.vmap(one)(shards)
    return jnp.sum(per, axis=0)


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("with_le", "direction", "last", "stacked"))
def _label_sharded(spec, shards, lv, les, *, with_le, direction, last,
                   stacked=True):
    _count("label", "scan")
    shards = _with_global_window(_lift(shards, stacked))

    def one(st):
        w, wl = _q.vertex_label_aggregate(
            spec.config, st, lv, direction=direction, with_edge_label=with_le,
            last=last, edge_label=les if with_le else None)
        return wl if with_le else w
    return jnp.sum(jax.vmap(one)(shards), axis=0)


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("with_le",))
def _edge_pallas(spec, planes, src, dst, la, lb, les, *, with_le):
    _count("edge", "pallas")
    from repro.kernels.sketch_query.ops import edge_query_planes
    w, wl = edge_query_planes(spec.config, planes, src, dst, (la, lb, les),
                              with_le=with_le)
    return jnp.sum(wl if with_le else w, axis=0)


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("with_le", "direction"))
def _vertex_pallas(spec, planes, v, lv, les, *, with_le, direction):
    _count("vertex", "pallas")
    from repro.kernels.vertex_scan.ops import vertex_query_planes
    w, wl = vertex_query_planes(spec.config, planes, v, (lv, les),
                                direction=direction, with_le=with_le)
    return jnp.sum(wl if with_le else w, axis=0)


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("with_le", "direction"))
def _label_pallas(spec, planes, lv, les, *, with_le, direction):
    _count("label", "pallas")
    from repro.kernels.vertex_scan.ops import label_aggregate_planes
    w, wl = label_aggregate_planes(spec.config, planes, lv, edge_label=les,
                                   direction=direction, with_le=with_le)
    return jnp.sum(wl if with_le else w, axis=0)


# --------------------------------------------------------------------------
# horizon-stacked dispatches (DESIGN.md §14): the same plane ops over a
# MultiPlanes — the ops collapse the leading [H] like a shard-axis
# singleton and return [H, B] already shard-reduced, so these return the
# op output directly (no outer sum).
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("with_le",))
def _edge_pallas_multi(spec, planes, src, dst, la, lb, les, *, with_le):
    _count("edge", "pallas-multi")
    from repro.kernels.sketch_query.ops import edge_query_planes
    w, wl = edge_query_planes(spec.config, planes, src, dst, (la, lb, les),
                              with_le=with_le)
    return wl if with_le else w


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("with_le", "direction"))
def _vertex_pallas_multi(spec, planes, v, lv, les, *, with_le,
                         direction):
    _count("vertex", "pallas-multi")
    from repro.kernels.vertex_scan.ops import vertex_query_planes
    w, wl = vertex_query_planes(spec.config, planes, v, (lv, les),
                                direction=direction, with_le=with_le)
    return wl if with_le else w


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("with_le", "direction"))
def _label_pallas_multi(spec, planes, lv, les, *, with_le, direction):
    _count("label", "pallas-multi")
    from repro.kernels.vertex_scan.ops import label_aggregate_planes
    w, wl = label_aggregate_planes(spec.config, planes, lv, edge_label=les,
                                   direction=direction, with_le=with_le)
    return wl if with_le else w


# --------------------------------------------------------------------------
# collective dispatches (DESIGN.md §9): the same plane ops inside
# shard_map over the shard axis — per-device shard blocks, psum reduction
# --------------------------------------------------------------------------

def _shmap(body, ctx, n_query_args):
    """shard_map wrapper shared by the collective dispatches: planes are
    sharded on the leading shard axis, query arrays replicated, output
    replicated (already psum-reduced inside the plane ops)."""
    return jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(ctx.axis),) + (P(),) * n_query_args,
        out_specs=P(), check_vma=False)


@functools.partial(jax.jit, static_argnums=(0, 1),
                   static_argnames=("with_le",))
def _edge_collective(spec, ctx, planes, src, dst, la, lb, les, *, with_le):
    _count("edge", "collective")
    from repro.kernels.sketch_query.ops import edge_query_planes

    def body(planes, src, dst, la, lb, les):
        w, wl = edge_query_planes(spec.config, planes, src, dst,
                                  (la, lb, les), with_le=with_le,
                                  axis_name=ctx.axis)
        return wl if with_le else w

    return _shmap(body, ctx, 5)(planes, src, dst, la, lb, les)


@functools.partial(jax.jit, static_argnums=(0, 1),
                   static_argnames=("with_le", "direction"))
def _vertex_collective(spec, ctx, planes, v, lv, les, *, with_le,
                       direction):
    _count("vertex", "collective")
    from repro.kernels.vertex_scan.ops import vertex_query_planes

    def body(planes, v, lv, les):
        w, wl = vertex_query_planes(spec.config, planes, v, (lv, les),
                                    direction=direction, with_le=with_le,
                                    axis_name=ctx.axis)
        return wl if with_le else w

    return _shmap(body, ctx, 3)(planes, v, lv, les)


@functools.partial(jax.jit, static_argnums=(0, 1),
                   static_argnames=("with_le", "direction"))
def _label_collective(spec, ctx, planes, lv, les, *, with_le, direction):
    _count("label", "collective")
    from repro.kernels.vertex_scan.ops import label_aggregate_planes

    def body(planes, lv, les):
        w, wl = label_aggregate_planes(spec.config, planes, lv,
                                       edge_label=les, direction=direction,
                                       with_le=with_le, axis_name=ctx.axis)
        return wl if with_le else w

    return _shmap(body, ctx, 2)(planes, lv, les)


def _shmap_multi(body, ctx, n_query_args):
    """shard_map wrapper for the horizon-stacked collective dispatches:
    the MultiPlanes shard on their axis-1 shard axis (horizon axis
    replicated), query arrays replicated, output replicated — the multi
    plane ops psum their [H, B] answers internally."""
    return jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(_multi_pspecs(ctx.axis),) + (P(),) * n_query_args,
        out_specs=P(), check_vma=False)


@functools.partial(jax.jit, static_argnums=(0, 1),
                   static_argnames=("with_le",))
def _edge_collective_multi(spec, ctx, planes, src, dst, la, lb, les, *,
                           with_le):
    _count("edge", "collective-multi")
    from repro.kernels.sketch_query.ops import edge_query_planes

    def body(planes, src, dst, la, lb, les):
        w, wl = edge_query_planes(spec.config, planes, src, dst,
                                  (la, lb, les), with_le=with_le,
                                  axis_name=ctx.axis)
        return wl if with_le else w

    return _shmap_multi(body, ctx, 5)(planes, src, dst, la, lb, les)


@functools.partial(jax.jit, static_argnums=(0, 1),
                   static_argnames=("with_le", "direction"))
def _vertex_collective_multi(spec, ctx, planes, v, lv, les, *, with_le,
                             direction):
    _count("vertex", "collective-multi")
    from repro.kernels.vertex_scan.ops import vertex_query_planes

    def body(planes, v, lv, les):
        w, wl = vertex_query_planes(spec.config, planes, v, (lv, les),
                                    direction=direction, with_le=with_le,
                                    axis_name=ctx.axis)
        return wl if with_le else w

    return _shmap_multi(body, ctx, 3)(planes, v, lv, les)


@functools.partial(jax.jit, static_argnums=(0, 1),
                   static_argnames=("with_le", "direction"))
def _label_collective_multi(spec, ctx, planes, lv, les, *, with_le,
                            direction):
    _count("label", "collective-multi")
    from repro.kernels.vertex_scan.ops import label_aggregate_planes

    def body(planes, lv, les):
        w, wl = label_aggregate_planes(spec.config, planes, lv,
                                       edge_label=les, direction=direction,
                                       with_le=with_le, axis_name=ctx.axis)
        return wl if with_le else w

    return _shmap_multi(body, ctx, 2)(planes, lv, les)


# --------------------------------------------------------------------------
# public entry
# --------------------------------------------------------------------------

def query(spec: SketchSpec, state, q: QueryBatch,
          path: str = "auto") -> jnp.ndarray:
    """Answer a QueryBatch against a sketch. int32 [B] out.

    ``state`` is normally a ``ShardedState`` handle; a plain per-shard state
    pytree (the object-shim path) is accepted too and lifted to a 1-shard
    stack *inside* the jitted dispatch (no eager whole-state copy).

    ``path``: "auto" (backend default), "scan" (dense vmapped reference),
    "pallas" (shard-axis kernels / compiled lowerings over cached
    window-reduced planes), or "collective" (the same plane walk inside
    ``shard_map`` over a mesh-resident handle — device-local shard blocks,
    device-resident plane cache, psum reduction; requires ``place``).
    All answer bit-identically (pinned in tests/test_query_path.py and
    tests/test_multidevice.py).

    A list/tuple ``q.last`` is a multi-horizon sweep: ``int32 [H, B]``
    out, row ``i`` bit-identical to ``query(..., last=q.last[i])`` — on
    the plane paths answered from ONE horizon-stacked build + one batched
    dispatch (DESIGN.md §14) rather than H dispatches.
    """
    if isinstance(q.last, (list, tuple)):
        return _query_multi(spec, state, q, path)
    path = resolve_query_path(spec, path)
    stacked = isinstance(state, ShardedState)
    shards = state.shards if stacked else state
    arrays, with_le, last, n = normalize_query(spec, q)

    if q.kind == "edge":
        src, dst, la, lb, les = arrays
        if path == "collective":
            ctx = _collective_ctx(spec, state)
            planes = query_planes(spec, state, last, collective=True)
            out = _edge_collective(spec, ctx, planes, src, dst, la, lb, les,
                                   with_le=with_le)
        elif path == "pallas":
            planes = query_planes(spec, state, last)
            out = _edge_pallas(spec, planes, src, dst, la, lb, les,
                               with_le=with_le)
        else:
            out = _edge_sharded(spec, shards, src, dst, la, lb, les,
                                with_le=with_le, last=last, stacked=stacked)
        return out[:n]

    if q.kind == "vertex":
        v, lv, les = arrays
        if path == "collective":
            ctx = _collective_ctx(spec, state)
            planes = query_planes(spec, state, last, collective=True)
            out = _vertex_collective(spec, ctx, planes, v, lv, les,
                                     with_le=with_le, direction=q.direction)
        elif path == "pallas":
            planes = query_planes(spec, state, last)
            out = _vertex_pallas(spec, planes, v, lv, les, with_le=with_le,
                                 direction=q.direction)
        else:
            out = _vertex_sharded(spec, shards, v, lv, les, with_le=with_le,
                                  direction=q.direction, last=last,
                                  stacked=stacked)
        return out[:n]

    if q.kind == "label":
        lv, les = arrays
        if path == "collective":
            ctx = _collective_ctx(spec, state)
            planes = query_planes(spec, state, last, collective=True)
            out = _label_collective(spec, ctx, planes, lv, les,
                                    with_le=with_le, direction=q.direction)
        elif path == "pallas":
            planes = query_planes(spec, state, last)
            out = _label_pallas(spec, planes, lv, les, with_le=with_le,
                                direction=q.direction)
        else:
            out = _label_sharded(spec, shards, lv, les, with_le=with_le,
                                 direction=q.direction, last=last,
                                 stacked=stacked)
        return out[:n]

    raise ValueError(f"unknown query kind {q.kind!r}")


def _query_multi(spec: SketchSpec, state, q: QueryBatch,
                 path: str = "auto") -> jnp.ndarray:
    """Multi-horizon sweep dispatch: int32 [H, B] out, rows in the order
    the user listed the horizons (duplicates and ``None`` welcome — the
    stacked build runs over the sorted unique clamp, rows are gathered
    back). The scan path loops the single-horizon reference per horizon
    (it has no plane reuse to exploit); the pallas/collective paths build
    one ``MultiPlanes`` and answer every horizon in one dispatch."""
    lasts = list(q.last)
    if not lasts:
        raise ValueError("multi-horizon query needs at least one horizon")
    path = resolve_query_path(spec, path)
    if spec.kind == "gss":
        # the window degenerates (normalize_query nulls `last`): one
        # answer serves every horizon
        out = query(spec, state, dataclasses.replace(q, last=None),
                    path=path)
        return jnp.broadcast_to(out[None], (len(lasts),) + out.shape)
    if path == "scan":
        outs = [query(spec, state, dataclasses.replace(
            q, last=None if h is None else int(h)), path=path)
            for h in lasts]
        return jnp.stack(outs)

    uniq, sel = _normalize_horizons(spec, lasts)
    collective = path == "collective"
    planes, _ = query_planes_multi(spec, state, lasts, collective=collective)
    arrays, with_le, _, n = normalize_query(
        spec, dataclasses.replace(q, last=None))

    if q.kind == "edge":
        src, dst, la, lb, les = arrays
        if collective:
            ctx = _collective_ctx(spec, state)
            out = _edge_collective_multi(spec, ctx, planes, src, dst, la, lb,
                                         les, with_le=with_le)
        else:
            out = _edge_pallas_multi(spec, planes, src, dst, la, lb, les,
                                     with_le=with_le)
    elif q.kind == "vertex":
        v, lv, les = arrays
        if collective:
            ctx = _collective_ctx(spec, state)
            out = _vertex_collective_multi(spec, ctx, planes, v, lv, les,
                                           with_le=with_le,
                                           direction=q.direction)
        else:
            out = _vertex_pallas_multi(spec, planes, v, lv, les,
                                       with_le=with_le,
                                       direction=q.direction)
    elif q.kind == "label":
        lv, les = arrays
        if collective:
            ctx = _collective_ctx(spec, state)
            out = _label_collective_multi(spec, ctx, planes, lv, les,
                                          with_le=with_le,
                                          direction=q.direction)
        else:
            out = _label_pallas_multi(spec, planes, lv, les, with_le=with_le,
                                      direction=q.direction)
    else:
        raise ValueError(f"unknown query kind {q.kind!r}")
    return out[jnp.asarray(sel, jnp.int32)][:, :n]
