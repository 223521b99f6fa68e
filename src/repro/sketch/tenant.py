"""TenantPool — many same-spec sketches behind one compiled program
(DESIGN.md §11).

The north star is heavy traffic from *many* independent users, each with
their own sketch. Handled naively that is one handle — one jitted program,
one dispatch, one plane cache — per tenant, and the host-side dispatch
fan-out dominates long before the device saturates. The pool generalizes
the shard-stacking idiom (DESIGN.md §6/§7) one level up: ``n_slots``
tenants' shard stacks are packed on the same leading axis, giving one
``ShardedState`` with ``n_slots * n_shards`` rows, and every cross-tenant
ingest or query collapses into the *same* single stacked dispatches the
plain sharded handle already uses.

Row layout and routing::

    pooled row = slot * n_shards + routed_assignment(tenant_spec, ...)

i.e. the tenant id folds into the routing exactly like the shard partition
does — a tenant's block of rows receives precisely the rows an independent
``n_shards`` handle would hold, in the same order, so every pooled answer
is **bit-identical** to the tenant's standalone sketch (property-tested in
tests/test_tenant_pool.py). The only cross-tenant coupling the stacked
layout could introduce — window reconciliation — is cut by the per-group
``cur_widx`` lift (``query._with_group_window``): each tenant's block
reconciles only within itself, never against another tenant's timeline.

Ingest reuses ``ingest._dispatch_stacked`` on the pool spec unchanged:
donation, mesh-context propagation, and the ``PlanesDelta`` incremental
plane maintenance (DESIGN.md §10) all apply to the pooled handle for free
(pooled planes live under ``("pooled", n_slots, horizon)`` cache keys and
delta-apply with the per-group window lift). ``submit``/``flush`` mirror
``AsyncIngestor``'s double-buffered pipeline: the numpy partition of the
next round overlaps the in-flight pooled dispatch.

Cross-tenant flush contract (the pooled extension of DESIGN.md §7.3):
within one tenant, batches apply in submission order — submissions are
concatenated per tenant before partitioning, and rounds dispatch in
order. Across tenants the pooled rows are disjoint, so cross-tenant order
cannot affect any state; the pool still *normalizes* it (tenants sort by
slot inside a round) so the partitioned layout, and therefore every
compiled shape and dispatch, is deterministic regardless of the iteration
order of the caller's dict/list.

Admission/eviction state machine (DESIGN.md §11): a tenant is either
**attached** (owns a slot) or **evicted** (its state lives in a per-tenant
checkpoint under ``directory``, tenant id recorded in the manifest's
``extra``). ``attach`` admits into a free slot — restoring the checkpoint
bit-identically if one exists — and when the pool is full either evicts
the coldest attached tenant (LRU over ingest/query touches; needs
``directory``) or raises ``PoolFullError``. Slots are interchangeable: a
tenant readmitted into a different slot answers identically (the routing
hash is slot-relative).

``path="collective"`` is not supported on pooled handles: the pool is the
*host-side* fan-out answer to many small tenants; mesh-resident serving of
one big sketch stays with the plain handle (DESIGN.md §9).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import queries as _q
from repro.core.lgs import _lgs_edge_query, _lgs_vertex_query
from repro.core.types import EMPTY, EdgeBatch
from repro.engine.window import bucket_size

from . import checkpoint as _ckpt
from .ingest import (_FIELDS, _degenerate_batch, _dispatch_stacked,
                     _shard_bucket)
from .query import (QueryBatch, _count, _normalize_horizons,
                    _with_group_window, query_planes, query_planes_multi,
                    resolve_query_path)
from .routing import routed_assignment
from .spec import SketchSpec
from .state import ShardedState, _init_one, create


class PoolFullError(RuntimeError):
    """Raised by ``attach`` when every slot is occupied and the pool has no
    checkpoint directory to evict cold tenants into."""


# --------------------------------------------------------------------------
# slot surgery — jitted row-block extraction/insertion on the pooled stack
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n",))
def _slice_rows(shards, start, *, n):
    """Extract one tenant's ``n``-row block (traced ``start``: one compiled
    program serves every slot)."""
    return jax.tree.map(
        lambda x: jax.lax.dynamic_slice_in_dim(x, start, n, axis=0), shards)


@functools.partial(jax.jit, donate_argnums=0)
def _update_rows(pool, rows, start):
    """Write one tenant's row block into the pooled stack (donating — slot
    surgery never copies the other tenants)."""
    return jax.tree.map(
        lambda p, r: jax.lax.dynamic_update_slice_in_dim(p, r, start, axis=0),
        pool, rows)


# --------------------------------------------------------------------------
# pooled query dispatches — a [groups, Lq] grid: every tenant's shard block
# answers only its own query rows (no cross-tenant broadcast), one dispatch
# --------------------------------------------------------------------------
#
# Query arrays arrive pre-grouped as [groups, Lq] (tenant g's rows in row
# g, EMPTY-padded); the state/planes reshape to [groups, per_shards, ...]
# and an outer vmap runs each group's block against its own row — so the
# pooled dispatch does the *same* total probe work as the independent
# handles it replaces, and the [groups, Lq] shape is fully static (no
# recompiles as the active-tenant mix shifts between drains). The
# within-group sum adds exactly the rows an independent handle would add
# (int32 — order-free), keeping answers bit-identical.

def _grouped(tree, groups: int):
    return jax.tree.map(
        lambda x: x.reshape((groups, -1) + x.shape[1:]), tree)


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("with_le", "last", "groups"))
def _edge_pooled(spec, shards, src, dst, la, lb, les, *, with_le, last,
                 groups):
    _count("edge", "pooled")
    gsh = _grouped(_with_group_window(shards, groups), groups)

    def per_group(gst, s_, d_, a_, b_, e_):
        if spec.kind == "lgs":
            per = jax.vmap(lambda st: _lgs_edge_query(
                spec.config.key(), st, s_, d_, a_, b_, e_, with_le, last))(
                    gst)
        else:
            def one(st):
                w, wl = _q.edge_query(spec.config, st, s_, d_,
                                      (a_, b_, e_), with_le, last)
                return wl if with_le else w
            per = jax.vmap(one)(gst)
        return jnp.sum(per, axis=0)

    return jax.vmap(per_group)(gsh, src, dst, la, lb, les)


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("with_le", "direction", "last", "groups"))
def _vertex_pooled(spec, shards, v, lv, les, *, with_le, direction, last,
                   groups):
    _count("vertex", "pooled")
    gsh = _grouped(_with_group_window(shards, groups), groups)

    def per_group(gst, v_, l_, e_):
        if spec.kind == "lgs":
            per = jax.vmap(lambda st: _lgs_vertex_query(
                spec.config.key(), st, v_, l_, e_, with_le, direction,
                last))(gst)
        else:
            def one(st):
                w, wl = _q.vertex_query(spec.config, st, v_, (l_, e_),
                                        direction=direction,
                                        with_edge_label=with_le, last=last)
                return wl if with_le else w
            per = jax.vmap(one)(gst)
        return jnp.sum(per, axis=0)

    return jax.vmap(per_group)(gsh, v, lv, les)


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("with_le", "direction", "last", "groups"))
def _label_pooled(spec, shards, lv, les, *, with_le, direction, last,
                  groups):
    _count("label", "pooled")
    gsh = _grouped(_with_group_window(shards, groups), groups)

    def per_group(gst, l_, e_):
        def one(st):
            w, wl = _q.vertex_label_aggregate(
                spec.config, st, l_, direction=direction,
                with_edge_label=with_le, last=last,
                edge_label=e_ if with_le else None)
            return wl if with_le else w
        return jnp.sum(jax.vmap(one)(gst), axis=0)

    return jax.vmap(per_group)(gsh, lv, les)


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("with_le", "groups"))
def _edge_pooled_planes(spec, planes, src, dst, la, lb, les, *, with_le,
                        groups):
    _count("edge", "pooled-pallas")
    from repro.kernels.sketch_query.ops import edge_query_planes

    def per_group(gpl, s_, d_, a_, b_, e_):
        w, wl = edge_query_planes(spec.config, gpl, s_, d_, (a_, b_, e_),
                                  with_le=with_le)
        return jnp.sum(wl if with_le else w, axis=0)

    return jax.vmap(per_group)(_grouped(planes, groups), src, dst, la, lb,
                               les)


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("with_le", "direction", "groups"))
def _vertex_pooled_planes(spec, planes, v, lv, les, *, with_le, direction,
                          groups):
    _count("vertex", "pooled-pallas")
    from repro.kernels.vertex_scan.ops import vertex_query_planes

    def per_group(gpl, v_, l_, e_):
        w, wl = vertex_query_planes(spec.config, gpl, v_, (l_, e_),
                                    direction=direction, with_le=with_le)
        return jnp.sum(wl if with_le else w, axis=0)

    return jax.vmap(per_group)(_grouped(planes, groups), v, lv, les)


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("with_le", "direction", "groups"))
def _label_pooled_planes(spec, planes, lv, les, *, with_le, direction,
                         groups):
    _count("label", "pooled-pallas")
    from repro.kernels.vertex_scan.ops import label_aggregate_planes

    def per_group(gpl, l_, e_):
        w, wl = label_aggregate_planes(spec.config, gpl, l_, edge_label=e_,
                                       direction=direction, with_le=with_le)
        return jnp.sum(wl if with_le else w, axis=0)

    return jax.vmap(per_group)(_grouped(planes, groups), lv, les)


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("kind", "k", "direction", "groups"))
def _topk_pooled_planes(spec, planes, *, kind, k, direction, groups):
    """Per-tenant heavy-hitter top-k over grouped pooled planes — the
    analytics portfolio (DESIGN.md §12) vmapped across tenant blocks, one
    dispatch for the whole pool. Each group's epilogue sees only its own
    tenant's rows, so results are bit-identical to the tenant's standalone
    handle."""
    _count("hh_" + kind, "pooled")
    from repro.kernels.heavy_hitters.ops import (
        heavy_edges_planes, heavy_vertices_planes, top_labels_planes)

    def per_group(gpl):
        if kind == "vertex":
            return heavy_vertices_planes(spec.config, gpl, k,
                                         direction=direction)
        if kind == "edge":
            return heavy_edges_planes(spec.config, gpl, k)
        return top_labels_planes(spec.config, gpl, k, direction=direction)

    return jax.vmap(per_group)(_grouped(planes, groups))


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("kind", "k", "direction", "groups"))
def _topk_pooled_planes_multi(spec, planes, *, kind, k, direction, groups):
    """Horizon-sweep twin of ``_topk_pooled_planes``: slice each horizon
    off the stacked pooled ``MultiPlanes`` (DESIGN.md §14), run the same
    grouped decode, and stack — the per-horizon decodes unroll inside ONE
    jitted program, so an H-point sweep still costs one dispatch."""
    _count("hh_" + kind, "pooled-multi")
    from repro.kernels.heavy_hitters.ops import (
        heavy_edges_planes, heavy_vertices_planes, top_labels_planes)

    def per_group(gpl):
        if kind == "vertex":
            return heavy_vertices_planes(spec.config, gpl, k,
                                         direction=direction)
        if kind == "edge":
            return heavy_edges_planes(spec.config, gpl, k)
        return top_labels_planes(spec.config, gpl, k, direction=direction)

    H = planes.cw.shape[0]
    outs = [jax.vmap(per_group)(_grouped(_q.slice_horizon(planes, i), groups))
            for i in range(H)]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *outs)


# --------------------------------------------------------------------------
# query-batch combination — many (tenant, QueryBatch) pairs, one dispatch
# --------------------------------------------------------------------------

def _batch_len(q: QueryBatch) -> int:
    if q.kind == "edge":
        return max(np.atleast_1d(np.asarray(q.src)).shape[0],
                   np.atleast_1d(np.asarray(q.dst)).shape[0])
    if q.kind == "vertex":
        return np.atleast_1d(np.asarray(q.vertex)).shape[0]
    return np.atleast_1d(np.asarray(q.vertex_label)).shape[0]


def _cat_field(vals, lens):
    """Concatenate one optional per-pair field, broadcasting scalars to
    their pair's row count; all-None stays None (with_le off)."""
    if all(v is None for v in vals):
        return None
    if any(v is None for v in vals):
        raise ValueError(
            "pooled query batches must agree on edge_label presence "
            "(with_le is a static axis of the compiled dispatch)")
    return np.concatenate([
        np.broadcast_to(np.atleast_1d(np.asarray(v, np.int32)), (n,))
        for v, n in zip(vals, lens)])


def _np_i32(x, n: int | None = None):
    a = np.atleast_1d(np.asarray(x, np.int32))
    if n is not None and a.shape[0] != n:
        a = np.broadcast_to(a, (n,))
    return a


def _np_query_rows(spec, q: QueryBatch):
    """Numpy twin of ``query.normalize_query`` minus the bucket pad: the
    pooled frontend fills a host-side ``[n_slots, Lq]`` EMPTY grid, and
    per-slot jnp normalization would cost more tiny device dispatches than
    the pooled dispatch saves (measured: it erased the whole win). Same
    semantics — int32, scalar broadcast, GSS degeneration (labels zeroed,
    edge-label/window dropped), LGS label rejection — asserted against the
    standalone frontend by the tests/test_tenant_pool.py bit-identity
    property. Returns ``(arrays, with_le, last, n)`` with unpadded
    ndarrays."""
    if q.kind == "edge":
        src, dst = _np_i32(q.src), _np_i32(q.dst)
        n = max(src.shape[0], dst.shape[0])
        src, dst = _np_i32(src, n), _np_i32(dst, n)
        la, lb = _np_i32(q.src_label, n), _np_i32(q.dst_label, n)
        le, last = q.edge_label, q.last
        if spec.kind == "gss":
            la, lb = np.zeros_like(la), np.zeros_like(lb)
            le = last = None
        with_le = le is not None
        les = _np_i32(le, n) if with_le else np.zeros_like(src)
        return (src, dst, la, lb, les), with_le, last, n
    if q.kind == "vertex":
        v = _np_i32(q.vertex)
        n = v.shape[0]
        lv = _np_i32(q.vertex_label, n)
        le, last = q.edge_label, q.last
        if spec.kind == "gss":
            lv, le, last = np.zeros_like(lv), None, None
        with_le = le is not None
        les = _np_i32(le, n) if with_le else np.zeros_like(v)
        return (v, lv, les), with_le, last, n
    if q.kind == "label":
        if spec.kind == "lgs":
            raise NotImplementedError(
                "LGS stores no label blocks; label aggregates need "
                "LSketch/GSS")
        lv = _np_i32(q.vertex_label)
        n = lv.shape[0]
        le, last = q.edge_label, q.last
        if spec.kind == "gss":
            lv, le, last = np.zeros_like(lv), None, None
        with_le = le is not None
        les = _np_i32(le, n) if with_le else np.zeros_like(lv)
        return (lv, les), with_le, last, n
    raise ValueError(f"unknown query kind {q.kind!r}")


def _group_queries(spec, slotted, n_slots: int):
    """Pack ``(slot, QueryBatch)`` pairs into the ``[n_slots, Lq]`` grouped
    arrays the pooled dispatches consume: each slot's pairs concatenate (in
    pair order) into row ``slot``, normalized as the standalone frontend
    would, padded to the common bucket ``Lq`` with the ``EMPTY`` sentinel;
    slots with no queries are all-EMPTY rows. All host-side numpy — one
    device transfer per field. kind / direction / last / edge-label
    presence must agree — they are static axes of the compiled dispatch
    (callers group heterogeneous traffic by them, as ``SketchServer``
    does).

    Returns ``(garrays, with_le, last, kind, direction, spans)`` where
    ``spans[i] = (slot, offset, length)`` locates pair ``i``'s answers in
    the ``[n_slots, Lq]`` output grid.
    """
    kinds = {q.kind for _, q in slotted}
    dirs = {q.direction for _, q in slotted}
    lasts = {q.last for _, q in slotted}
    if len(kinds) > 1 or len(dirs) > 1 or len(lasts) > 1:
        raise ValueError(
            f"pooled query batches must share kind/direction/last, got "
            f"kinds={sorted(kinds)} directions={sorted(dirs)} "
            f"lasts={sorted(lasts, key=repr)}")
    kind = next(iter(kinds))
    direction = next(iter(dirs))
    by_slot: dict[int, list[int]] = {}
    for i, (s, _) in enumerate(slotted):
        by_slot.setdefault(s, []).append(i)
    fields = ("src", "src_label", "dst", "dst_label", "vertex",
              "vertex_label", "edge_label")
    spans: list = [None] * len(slotted)
    slot_norm: dict[int, tuple] = {}
    with_le = last = None
    for s, idxs in by_slot.items():
        qs = [slotted[i][1] for i in idxs]
        lens = [_batch_len(q) for q in qs]
        cat = {f: _cat_field([getattr(q, f) for q in qs], lens)
               for f in fields}
        sb = QueryBatch(kind=kind, direction=direction,
                        last=next(iter(lasts)), **cat)
        arrays, wle, lst, _n = _np_query_rows(spec, sb)
        if with_le is None:
            with_le, last = wle, lst
        elif wle != with_le:
            raise ValueError(
                "pooled query batches must agree on edge_label presence "
                "(with_le is a static axis of the compiled dispatch)")
        slot_norm[s] = arrays
        off = 0
        for i, m in zip(idxs, lens):
            spans[i] = (s, off, m)
            off += m
    Lq = bucket_size(max(a[0].shape[0] for a in slot_norm.values()),
                     floor=32)
    grouped = [np.full((n_slots, Lq), EMPTY, np.int32)
               for _ in next(iter(slot_norm.values()))]
    for s, arrays in slot_norm.items():
        for gi, a in enumerate(arrays):
            grouped[gi][s, :a.shape[0]] = a
    garrays = tuple(jnp.asarray(g) for g in grouped)
    return garrays, with_le, last, kind, direction, spans


# --------------------------------------------------------------------------
# the pool
# --------------------------------------------------------------------------

class TenantPool:
    """``n_slots`` same-spec tenant sketches in one stacked handle.

    ``spec`` is the *per-tenant* spec (its ``n_shards`` is each tenant's
    shard count); the pooled handle lives under ``pool_spec`` =
    ``spec.replace(n_shards=n_slots * spec.n_shards)`` and flows through
    the ordinary sharded ingest/checkpoint machinery unchanged.

    ``directory`` (optional) enables the eviction side of the admission
    machinery: evicted tenants checkpoint under
    ``directory/tenant-<id>`` with the tenant id in the manifest ``extra``,
    and ``attach`` of a full pool auto-evicts the least-recently-used
    tenant instead of raising ``PoolFullError``.

    Write API mirrors ``AsyncIngestor``: ``submit`` stages a round of
    ``(tenant, batch)`` pairs (partitioning on the host while the previous
    round's pooled dispatch runs), ``flush``/``state`` synchronize.
    ``ingest`` is the submit+flush convenience. Reads (``query`` /
    ``query_many``) flush implicitly — they always see every submitted
    batch.
    """

    def __init__(self, spec: SketchSpec, n_slots: int, *, directory=None,
                 path: str = "auto", keep: int = 3):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        self.spec = spec
        self.n_slots = int(n_slots)
        self.pool_spec = spec.replace(n_shards=self.n_slots * spec.n_shards)
        self.directory = directory
        self.path = path
        self.keep = keep
        self._state = create(self.pool_spec)
        self._slots: dict = {}       # tenant id -> slot
        self._last_used: dict = {}   # tenant id -> LRU clock tick
        self._steps: dict = {}       # tenant id -> next checkpoint step
        self._clock = 0
        self._staged = None          # (stacked EdgeBatch, n_valid) in flight
        self._empty_rows = None      # cached zero block for slot clearing

    # ---- introspection ----------------------------------------------------

    @property
    def tenants(self) -> dict:
        """Attached tenants: ``{tenant_id: slot}`` (copy)."""
        return dict(self._slots)

    @property
    def free_slots(self) -> int:
        return self.n_slots - len(self._slots)

    @property
    def state(self) -> ShardedState:
        """The pooled handle with every submitted round applied (implicit
        flush). Like ``AsyncIngestor.state``, the returned handle is live —
        the next dispatched round donates its buffers."""
        return self.flush()

    def slot_of(self, tenant_id) -> int:
        """The attached slot of a tenant (KeyError when evicted/unknown)."""
        return self._slots[tenant_id]

    def handle_of(self, tenant_id) -> tuple[SketchSpec, ShardedState]:
        """A standalone ``(spec, state)`` copy of one tenant's sketch —
        the tenant's row block extracted into its own ``n_shards`` handle
        (fresh buffers; the pool is not aliased)."""
        st = self.flush()
        slot = self._slots[tenant_id]
        rows = _slice_rows(st.shards, slot * self.spec.n_shards,
                           n=self.spec.n_shards)
        return self.spec, ShardedState(shards=jax.tree.map(jnp.copy, rows))

    # ---- admission / eviction --------------------------------------------

    def _tenant_dir(self, tenant_id):
        import os
        return os.path.join(str(self.directory), f"tenant-{tenant_id}")

    def _has_checkpoint(self, tenant_id) -> bool:
        import os
        return (self.directory is not None
                and os.path.isdir(self._tenant_dir(tenant_id)))

    def attach(self, tenant_id) -> int:
        """Admit a tenant: returns its slot (existing, if already attached).

        A previously evicted tenant restores from its checkpoint
        bit-identically — possibly into a different slot (slots are
        interchangeable; routing is slot-relative). A full pool evicts its
        LRU tenant first when a ``directory`` is configured, else raises
        ``PoolFullError``.
        """
        if tenant_id in self._slots:
            return self._slots[tenant_id]
        if not self.free_slots:
            if self.directory is None:
                raise PoolFullError(
                    f"all {self.n_slots} slots attached and no checkpoint "
                    "directory to evict into — construct the pool with "
                    "directory=... or evict() a tenant explicitly")
            coldest = min(self._slots, key=lambda t: self._last_used[t])
            self.evict(coldest)
        slot = min(set(range(self.n_slots)) - set(self._slots.values()))
        if self._has_checkpoint(tenant_id):
            restored = _ckpt.restore(self.spec, self._tenant_dir(tenant_id))
            self._write_slot(slot, restored.shards)
        self._slots[tenant_id] = slot
        self._touch(tenant_id)
        return slot

    def evict(self, tenant_id, blocking: bool = True) -> None:
        """Checkpoint a tenant's rows (tenant id in the manifest ``extra``)
        and free its slot (rows reset to empty). Requires ``directory``."""
        if self.directory is None:
            raise ValueError("evict() needs a pool checkpoint directory")
        slot = self._slots[tenant_id]
        st = self.flush()
        rows = _slice_rows(st.shards, slot * self.spec.n_shards,
                           n=self.spec.n_shards)
        step = self._steps.get(tenant_id, 0)
        _ckpt.save(self.spec, ShardedState(shards=rows),
                   self._tenant_dir(tenant_id), step=step, keep=self.keep,
                   blocking=blocking, extra={"tenant_id": str(tenant_id)})
        self._steps[tenant_id] = step + 1
        self._clear_slot(slot)
        del self._slots[tenant_id]
        self._last_used.pop(tenant_id, None)

    def _touch(self, tenant_id) -> None:
        self._clock += 1
        self._last_used[tenant_id] = self._clock

    def _ensure(self, tenant_id) -> int:
        slot = self._slots.get(tenant_id)
        if slot is None:
            slot = self.attach(tenant_id)
        self._touch(tenant_id)
        return slot

    def _write_slot(self, slot: int, rows) -> None:
        """Replace one slot's row block (flushes first — slot surgery and
        pipelined ingest must not reorder). The handle object changes, so
        the plane cache invalidates by construction."""
        st = self.flush()
        shards = _update_rows(st.shards, rows,
                              jnp.int32(slot * self.spec.n_shards))
        self._state = ShardedState(shards=shards)

    def _clear_slot(self, slot: int) -> None:
        if self._empty_rows is None:
            base = _init_one(self.spec)
            self._empty_rows = jax.tree.map(
                lambda x: jnp.stack([x] * self.spec.n_shards), base)
        self._write_slot(slot, self._empty_rows)

    # ---- ingest -----------------------------------------------------------

    def _partition_pool(self, pairs):
        """Host half of a pooled round: the stable hash partition of every
        tenant's (concatenated, submission-ordered) rows into the pooled
        row layout. Pure numpy — overlapped with the in-flight dispatch by
        ``submit``. Pooled twin of ``ingest._partition_stack``."""
        n_sh = self.spec.n_shards
        S = self.pool_spec.n_shards
        # per-tenant concatenation in submission order, tenants normalized
        # by slot (cross-tenant rows are disjoint; sorting just makes the
        # layout deterministic under any caller iteration order)
        per_slot: dict = {}
        for slot, batch in pairs:
            if self.spec.kind == "gss":
                batch = _degenerate_batch(batch)
            fs = {f: np.atleast_1d(np.asarray(getattr(batch, f)))
                  for f in _FIELDS}
            if slot in per_slot:
                per_slot[slot] = {
                    f: np.concatenate([per_slot[slot][f], fs[f]])
                    for f in _FIELDS}
            else:
                per_slot[slot] = fs
        index: dict = {}
        max_count = 1
        for slot in sorted(per_slot):
            fs = per_slot[slot]
            # routing-aware like ingest._partition_stack: the tenant spec's
            # split table must steer pooled rows exactly as a standalone
            # handle's, or pooled answers stop being bit-identical to it
            sid = routed_assignment(self.spec, fs["src"], fs["dst"],
                                    fs["src_label"])
            for s in range(n_sh):
                ix = np.flatnonzero(sid == s)
                if len(ix):
                    index[slot * n_sh + s] = (fs, ix)
                    max_count = max(max_count, len(ix))
        L = _shard_bucket(max_count, floor=64)
        out = {f: np.zeros((S, L), np.int32) for f in _FIELDS}
        counts = np.zeros(S, np.int32)
        for row, (fs, ix) in index.items():
            m = len(ix)
            counts[row] = m
            for f in _FIELDS:
                r = out[f][row]
                r[:m] = fs[f][ix]
                r[m:] = r[m - 1]  # replicate-last keeps time non-decreasing
        stacked = EdgeBatch(**{f: jnp.asarray(out[f]) for f in _FIELDS})
        return stacked, jnp.asarray(counts)

    def submit(self, batches) -> None:
        """Stage one round of writes: ``{tenant: batch}`` or an iterable of
        ``(tenant, batch)`` pairs (a tenant may appear multiple times; its
        batches apply in pair order). Dispatches the previously staged
        round (async), then partitions this one on the host — the same
        one-round stagger as ``AsyncIngestor.submit``. Unknown tenants are
        admitted (``attach``), which may evict under a full pool."""
        pairs = (list(batches.items()) if isinstance(batches, dict)
                 else list(batches))
        pairs = [(tid, b) for tid, b in pairs
                 if int(np.atleast_1d(np.asarray(b.src)).shape[0]) > 0]
        if not pairs:
            return
        # admission may evict (slot surgery), which itself flushes — do it
        # before staging so the staged round can never be reordered past it
        slotted = [(self._ensure(tid), b) for tid, b in pairs]
        self._dispatch_staged()
        self._staged = self._partition_pool(slotted)

    def ingest(self, tenant_id, batch: EdgeBatch) -> None:
        """Synchronous single-tenant write (submit + flush)."""
        self.submit([(tenant_id, batch)])
        self.flush()

    def flush(self) -> ShardedState:
        """Dispatch any staged round; the returned pooled handle reflects
        every submitted batch, in per-tenant submission order."""
        self._dispatch_staged()
        return self._state

    @property
    def pending(self) -> int:
        """Staged-but-not-dispatched rounds (0 or 1)."""
        return int(self._staged is not None)

    @property
    def dispatched(self) -> ShardedState:
        """The live pooled handle with every *dispatched* round applied —
        does not flush the staged round (``AsyncIngestor.dispatched``
        semantics: serving loops prewarm planes off this without
        collapsing the pipeline stagger)."""
        return self._state

    def _dispatch_staged(self) -> None:
        if self._staged is None:
            return
        stacked, n_valid = self._staged
        self._staged = None
        self._state = _dispatch_stacked(self.pool_spec, self._state, stacked,
                                        n_valid, self.path)

    # ---- query ------------------------------------------------------------

    def prewarm(self, last=None, *, horizons=None) -> None:
        """Build (or delta-refresh) the pooled ``QueryPlanes`` for a window
        horizon ahead of traffic — the pooled twin of the serving loop's
        plane prewarm (DESIGN.md §8/§10). ``horizons=[h1, ..., hH]``
        prewarms the whole sweep in one fused multi-horizon build
        (DESIGN.md §14) that later ``top_k_many(horizons=...)`` calls and
        single-horizon lookups slice into."""
        if horizons is not None:
            query_planes_multi(self.spec, self.flush(), list(horizons),
                               groups=self.n_slots)
            return
        query_planes(self.spec, self.flush(), last, groups=self.n_slots)

    def query(self, tenant_id, q: QueryBatch, path: str = "auto"):
        """Answer one tenant's QueryBatch; int32 [B], bit-identical to the
        tenant's standalone sketch."""
        return self.query_many([(tenant_id, q)], path=path)[0]

    def query_many(self, pairs, path: str = "auto"):
        """Answer many ``(tenant, QueryBatch)`` pairs in **one** pooled
        dispatch; returns the per-pair answer arrays, in input order. The
        pairs must share kind/direction/last/edge-label-presence (the
        static axes of the compiled program — group heterogeneous traffic
        by those, as ``SketchServer`` does). Evicted tenants are readmitted
        on touch."""
        pairs = list(pairs.items()) if isinstance(pairs, dict) else list(pairs)
        if not pairs:
            return []
        path = resolve_query_path(self.spec, path)
        if path == "collective":
            raise ValueError(
                "pooled handles are host-resident: path='collective' is for "
                "one mesh-placed sketch (DESIGN.md §9), not a TenantPool")
        slotted = [(self._ensure(tid), q) for tid, q in pairs]
        state = self.flush()
        groups = self.n_slots
        garrays, with_le, last, kind, direction, spans = _group_queries(
            self.spec, slotted, groups)
        if path == "pallas":
            planes = query_planes(self.spec, state, last, groups=groups)
            if kind == "edge":
                out = _edge_pooled_planes(
                    self.spec, planes, *garrays, with_le=with_le,
                    groups=groups)
            elif kind == "vertex":
                out = _vertex_pooled_planes(
                    self.spec, planes, *garrays, with_le=with_le,
                    direction=direction, groups=groups)
            else:
                out = _label_pooled_planes(
                    self.spec, planes, *garrays, with_le=with_le,
                    direction=direction, groups=groups)
        else:
            if kind == "edge":
                out = _edge_pooled(self.spec, state.shards, *garrays,
                                   with_le=with_le, last=last, groups=groups)
            elif kind == "vertex":
                out = _vertex_pooled(self.spec, state.shards, *garrays,
                                     with_le=with_le, direction=direction,
                                     last=last, groups=groups)
            else:
                out = _label_pooled(self.spec, state.shards, *garrays,
                                    with_le=with_le, direction=direction,
                                    last=last, groups=groups)
        return [out[s, off:off + m] for s, off, m in spans]

    def top_k(self, tenant_id, kind: str = "vertex", k: int = 10, *,
              direction: str = "out", last=None, horizons=None):
        """One tenant's windowed heavy-hitter top-k (DESIGN.md §12):
        ``kind`` "vertex" -> (vids [k], weights [k]), "edge" ->
        (src [k], dst [k], weights [k]), "label" -> (blocks [k],
        weights [k]); (-1, 0) padding past the live identities.
        ``horizons=`` sweeps the ranking (leading ``[H]`` axis,
        DESIGN.md §14)."""
        return self.top_k_many([tenant_id], kind=kind, k=k,
                               direction=direction, last=last,
                               horizons=horizons)[0]

    def top_k_many(self, tenant_ids, kind: str = "vertex", k: int = 10, *,
                   direction: str = "out", last=None, horizons=None):
        """Heavy-hitter top-k for many tenants in **one** pooled dispatch.

        The grouped planes are the same cached ``query_planes(...,
        groups=n_slots)`` entry ``query_many`` uses; the top-k epilogue is
        vmapped across tenant blocks, so every tenant's answer is
        bit-identical to running ``repro.sketch.heavy_vertices`` (etc.) on
        its standalone handle. Returns per-tenant result tuples, in input
        order. Evicted tenants are readmitted on touch.

        ``horizons=[h1, ..., hH]`` (exclusive with ``last=``) sweeps the
        ranking across time horizons — each tenant's result leaves gain a
        leading ``[H]`` axis, row ``i`` bit-identical to
        ``last=horizons[i]`` — served from one fused multi-horizon pooled
        plane build (DESIGN.md §14)."""
        if self.spec.kind == "lgs":
            raise NotImplementedError(
                "LGS cells store no keys — the reversible cell-owner "
                "decode needs LSketch/GSS")
        if horizons is not None and last is not None:
            raise ValueError("pass either last= (one horizon) or horizons= "
                             "(a sweep), not both")
        tenant_ids = list(tenant_ids)
        if not tenant_ids:
            return []
        if horizons is not None:
            horizons = list(horizons)
            if not horizons:
                raise ValueError("horizons= needs at least one horizon")
            if self.spec.kind == "gss":  # no window ring: one ranking
                out = self.top_k_many(tenant_ids, kind=kind, k=k,
                                      direction=direction)
                return [jax.tree.map(
                    lambda x: jnp.broadcast_to(x[None],
                                               (len(horizons),) + x.shape),
                    o) for o in out]
            slots = [self._ensure(tid) for tid in tenant_ids]
            state = self.flush()
            _, sel = _normalize_horizons(self.spec, horizons)
            planes, _ = query_planes_multi(self.spec, state, horizons,
                                           groups=self.n_slots)
            out = _topk_pooled_planes_multi(
                self.spec, planes, kind=kind, k=k, direction=direction,
                groups=self.n_slots)
            sel_arr = jnp.asarray(sel, jnp.int32)
            out = jax.tree.map(lambda x: x[sel_arr], out)
            return [jax.tree.map(lambda x: x[:, s], out) for s in slots]
        slots = [self._ensure(tid) for tid in tenant_ids]
        state = self.flush()
        last = None if self.spec.kind == "gss" else last
        planes = query_planes(self.spec, state, last, groups=self.n_slots)
        out = _topk_pooled_planes(
            self.spec, planes, kind=kind, k=k, direction=direction,
            groups=self.n_slots)
        return [jax.tree.map(lambda x: x[s], out) for s in slots]
