"""Hash-partitioned sharded ingest (DESIGN.md §6/§7).

``ingest(spec, state, batch)`` is the one write path of the handle layer:

  1. the host partitions the time-ordered batch by the shard hash
     (``spec.shard_assignment`` of the source endpoint entity), preserving
     stream order inside each shard — a stable partition of a time-ordered
     stream is itself time-ordered per shard;
  2. every shard's sub-batch is padded to one common power-of-two bucket
     (replicate-last padding keeps ``time`` non-decreasing; a per-shard
     ``n_valid`` masks the padding completely, including ring bookkeeping,
     so even an empty shard is a strict no-op);
  3. one jitted dispatch runs the engine's **stacked** fused insert
     (``engine.insert.insert_stacked_fused_impl``) over the whole
     ``[n_shards, ...]`` stack: on the Pallas path every single-subwindow
     batch is one shard-axis kernel launch (grid ``(n_shards, n_blocks,
     n_blocks)``); the vmapped ``lax.scan`` takes multi-subwindow batches
     inside the same dispatch. The insert path follows the engine's
     selection rule (``engine.insert.resolve_path``): the binned path by
     default on TPU, compiled scan elsewhere.

``ingest_single`` is the unstacked 1-shard path the object shims
(``LSketch``/``LGS``/``GSS``) ride: no partition, no stacking copies, and
the full engine path choice on the plain state.

``AsyncIngestor`` double-buffers the host half against the device half:
the numpy hash-partition of batch N+1 runs while batch N's dispatch is in
flight (JAX async dispatch returns control as soon as the work is
enqueued). ``flush()`` is the synchronization point — after it, ``state``
reflects every submitted batch, in submission order (DESIGN.md §7.3).

Every write path here returns a **new** handle object; the kernel query
path's window-plane cache (DESIGN.md §8) memoizes on handle identity, so
any ingest — including the pipelined dispatches — invalidates it by
construction: a query after an ingest can never observe stale planes.

Mesh residency (DESIGN.md §9): a handle that was ``place``d carries a
``MeshContext``; every dispatch here first lays the host partition over
that same shard-axis sharding (each shard's rows go straight to the
device owning the shard — no gather through one device) and attaches the
context to the fresh handle, so ingest never demotes a mesh-resident
handle back to the host.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.lgs import _lgs_insert_fused, lgs_insert_impl
from repro.core.types import EdgeBatch
from repro.engine import insert as eng_insert
from repro.engine.window import pad_to_bucket

from .spec import SketchSpec
from .routing import HeavyKeyDetector, routed_assignment
from .state import ShardedState, create, mesh_context, with_mesh
from . import query as _query
from . import trace

_FIELDS = ("src", "dst", "src_label", "dst_label", "edge_label", "weight",
           "time")


def _degenerate_batch(batch: EdgeBatch) -> EdgeBatch:
    """GSS ignores labels and timestamps — normalize them away so the
    functional path matches the ``GSS`` object semantics exactly."""
    z = jnp.zeros_like(jnp.asarray(batch.src, jnp.int32))
    return EdgeBatch(src=batch.src, dst=batch.dst, src_label=z, dst_label=z,
                     edge_label=z, weight=batch.weight, time=z)


# --------------------------------------------------------------------------
# single-shard (unstacked) path — the compatibility-shim seat
# --------------------------------------------------------------------------

def ingest_single(spec: SketchSpec, state, batch: EdgeBatch,
                  path: str = "auto"):
    """Insert a batch into one plain (unstacked) shard state.

    This is the path ``LSketch``/``LGS``/``GSS`` objects delegate to with
    their implicit ``n_shards=1`` spec; it preserves the engine's insert-path
    selection (``path=``) and donation behaviour bit-for-bit.
    """
    n = int(batch.src.shape[0])
    if n == 0:
        return state
    if spec.kind == "gss":
        batch = _degenerate_batch(batch)
    if spec.kind == "lgs":
        arrs = [pad_to_bucket(jnp.asarray(getattr(batch, f), jnp.int32))
                for f in _FIELDS]
        arrs[5] = arrs[5].at[n:].set(0)  # padded weights are inert
        return _lgs_insert_fused(spec.config.key(), state, *arrs)
    return eng_insert.insert_batch(spec.config, state, batch, path=path)


# --------------------------------------------------------------------------
# sharded path
# --------------------------------------------------------------------------

def _shard_bucket(n: int, floor: int = 64) -> int:
    """Per-shard row-length bucket: powers of two plus the 1.5x midpoints
    (64, 96, 128, 192, 256, ...). The hash partition leaves every shard
    just above/below n/n_shards, so pure doubling would pad rows by up to
    2x — worst exactly in the common balanced case; the midpoints cap
    padding at 33% for ~2x the (still O(log max_batch)) compile count."""
    b = floor
    while b < n:
        if n <= b + b // 2:
            return b + b // 2
        b *= 2
    return b


def _partition_stack(spec: SketchSpec, batch: EdgeBatch):
    """Host-side stable hash partition -> (stacked EdgeBatch [n_shards, L],
    n_valid int32 [n_shards]). Pure numpy — this is the half the
    ``AsyncIngestor`` overlaps with the in-flight device dispatch.

    Routing-aware (DESIGN.md §13): a spec carrying a ``RoutingTable``
    scatters split hot keys over their replica shards via the salted
    ``(src, dst)`` hash; without one, ``routed_assignment`` degenerates
    to the plain endpoint hash bit-for-bit. While ``repro.sketch.trace``
    is on, each call is an ``ingest.partition`` span and, after it, adds
    to the ``ingest.batches``, ``ingest.rows``, ``ingest.pad_rows``
    (padding dispatched, ``n_shards * L - rows``) and
    ``ingest.boundary_batches`` (some shard's valid rows span several
    subwindows, so the stacked insert takes the scan, not the key walk)
    counters.
    """
    with trace.span("ingest.partition"):
        fields = {f: np.asarray(getattr(batch, f)) for f in _FIELDS}
        sid = routed_assignment(spec, fields["src"], fields["dst"],
                                fields["src_label"])
        n_sh = spec.n_shards
        index = [np.flatnonzero(sid == s) for s in range(n_sh)]
        counts = np.array([len(ix) for ix in index], np.int32)
        L = _shard_bucket(max(int(counts.max()), 1), floor=64)
        out = {f: np.zeros((n_sh, L), np.int32) for f in _FIELDS}
        for s, ix in enumerate(index):
            m = len(ix)
            if m == 0:
                continue  # all-zero row, fully masked by n_valid == 0
            for f in _FIELDS:
                row = out[f][s]
                row[:m] = fields[f][ix]
                row[m:] = row[m - 1]  # replicate-last: time non-decreasing
        stacked = EdgeBatch(**{f: jnp.asarray(out[f]) for f in _FIELDS})
    if trace.enabled():
        _count_partition(spec, out["time"], counts)
    return stacked, jnp.asarray(counts)


def _count_partition(spec: SketchSpec, time, counts) -> None:
    """The partition's counters (see ``_partition_stack``) from the
    stacked ``time`` rows: a row's padding repeats its last valid row, so
    a subwindow change between neighbours lies in the valid rows, as the
    device's ``one_segment_all`` test counts it."""
    rows = int(counts.sum())
    widx = time // spec.config.subwindow_size
    trace.count("ingest.batches")
    trace.count("ingest.rows", rows)
    trace.count("ingest.pad_rows", time.size - rows)
    trace.count("ingest.boundary_batches",
                int(np.any(widx[:, 1:] != widx[:, :-1])))


@functools.partial(jax.jit, static_argnums=(0, 1),
                   static_argnames=("use_pallas", "emit_delta"),
                   donate_argnums=2)
def _ingest_stacked_lsketch(cfg, ctx, shards, batch: EdgeBatch, n_valid,
                            use_pallas=False, emit_delta=False):
    """The stacked insert; with a ``MeshContext`` whose axis divides the
    shard count, each device runs it on its own shard block under
    ``shard_map`` (shards are independent, and GSPMD cannot partition the
    Pallas key walk)."""
    def run(sh, b, nv):
        return eng_insert.insert_stacked_fused_impl(
            cfg, sh, b, nv, use_pallas=use_pallas, emit_delta=emit_delta)

    if ctx is None:
        return run(shards, batch, n_valid)
    return jax.shard_map(run, mesh=ctx.mesh, in_specs=P(ctx.axis),
                         out_specs=P(ctx.axis), check_vma=False)(
                             shards, batch, n_valid)


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=1)
def _ingest_stacked_lgs(key, shards, batch: EdgeBatch, n_valid):
    def one(st, b, nv):
        valid = jnp.arange(b.src.shape[0], dtype=jnp.int32) < nv
        w = b.weight * valid.astype(b.weight.dtype)
        return lgs_insert_impl(key, st, b.src, b.dst, b.src_label,
                               b.dst_label, b.edge_label, w, b.time,
                               valid=valid)
    return jax.vmap(one)(shards, batch, n_valid)


def _place_partition(ctx, stacked: EdgeBatch, n_valid):
    """Lay a host partition over the handle's mesh before dispatch: each
    shard's rows transfer straight to the device that owns that shard, so
    the stacked insert compiles shard-local (GSPMD never gathers the
    partition — or the donated state — through one device)."""
    rows = NamedSharding(ctx.mesh, P(ctx.axis, None))
    vec = NamedSharding(ctx.mesh, P(ctx.axis))
    stacked = jax.tree.map(lambda x: jax.device_put(x, rows), stacked)
    return stacked, jax.device_put(n_valid, vec)


def _dispatch_stacked(spec: SketchSpec, state: ShardedState, stacked,
                      n_valid, path: str) -> ShardedState:
    """One jitted dispatch for a pre-partitioned stack (shared by
    ``ingest`` and ``AsyncIngestor``); donates the input handle. A
    mesh-resident handle (``place``) keeps its residency: the partition is
    placed under the same shard-axis sharding and the new handle carries
    the MeshContext forward. An ``ingest.dispatch`` span while
    ``repro.sketch.trace`` is on: the host's time to enqueue.

    Plane propagation (DESIGN.md §10): when the consumed handle carries
    cached ``QueryPlanes`` (or an unresolved delta chain), the dispatch
    also emits this flush's ``PlanesDelta`` and hangs the
    ``(parent planes, chain)`` off the fresh handle, so the next query
    can delta-apply instead of rebuilding. The emission flag is static —
    a handle that was never queried ingests with zero delta overhead."""
    with trace.span("ingest.dispatch"):
        ctx = mesh_context(state)
        local = ctx if ctx is not None and ctx.divides(spec.n_shards) \
            else None
        if local is not None:
            stacked, n_valid = _place_partition(ctx, stacked, n_valid)
        delta = carry = None
        if spec.kind == "lgs":
            shards = _ingest_stacked_lgs(spec.config.key(), state.shards,
                                         stacked, n_valid)
        else:
            path = eng_insert.resolve_path(spec.config, path)
            if path == "chunked":
                raise ValueError("the stacked ingest has no chunked path")
            carry = _query.planes_delta_base(state)
            out = _ingest_stacked_lsketch(
                spec.config, local, state.shards, stacked, n_valid,
                use_pallas=path == "pallas", emit_delta=carry is not None)
            shards, delta = out if carry is not None else (out, None)
        new = with_mesh(ShardedState(shards=shards), ctx)
        if carry is not None:
            _query.attach_planes_delta(new, carry[0], carry[1], delta)
        return new


def ingest(spec: SketchSpec, state: ShardedState, batch: EdgeBatch,
           path: str = "auto") -> ShardedState:
    """Insert a time-ordered batch into a sharded handle; returns the new
    handle (the input's buffers are donated). Every shard count — including
    1 — goes through the same stacked dispatch, so no eager unstack/restack
    copies; ``path`` follows the engine's selection rule ("auto" = Pallas
    kernel on TPU, fused scan elsewhere). Object shims that need the
    engine's unstacked entry use ``ingest_single`` instead."""
    n = int(batch.src.shape[0])
    if n == 0:
        return state
    if spec.kind == "gss":
        batch = _degenerate_batch(batch)
    stacked, n_valid = _partition_stack(spec, batch)
    return _dispatch_stacked(spec, state, stacked, n_valid, path)


# --------------------------------------------------------------------------
# pipelined ingest
# --------------------------------------------------------------------------

class AsyncIngestor:
    """Double-buffered pipelined ingest over one sharded handle.

    The sharded write path has a host half (the numpy hash partition) and
    a device half (the stacked jitted insert). Called naively they
    serialize: partition batch N, dispatch batch N, partition batch N+1,
    ... This class staggers them by one batch:

      * ``submit(batch)`` first issues the *previously staged* batch's
        device dispatch (async — returns as soon as it is enqueued), then
        hash-partitions this batch on the host while that dispatch runs;
      * ``flush()`` dispatches whatever is staged and returns the handle —
        the synchronization point. After ``flush()``, the state reflects
        every submitted batch, in exact submission order (dispatches are
        issued in order and each consumes the previous handle, so no
        reordering is possible across subwindow boundaries).

    ``state`` flushes implicitly — reading it always gives the synchronous
    semantics; the pipeline only ever defers work, never reorders it.

    Donation caveat: like ``ingest``, every dispatch donates the previous
    handle's buffers — the handle ``flush()``/``state`` returns is the
    *live* one and is consumed by the next dispatched batch. Query it
    before the next ``submit``, or snapshot it first
    (``jax.tree.map(jnp.copy, st.shards)``) if it must outlive the
    pipeline.

    Skew-aware routing (DESIGN.md §13): with ``heat_threshold`` set, a
    ``HeavyKeyDetector`` (space-saving summary) rides the host partition
    pass; any source endpoint past the threshold fraction of the stream
    is **split** — its edges scatter over ``split_replicas`` consecutive
    shards by a salted ``(src, dst)`` hash from this batch on. The split
    mutates ``self.spec``'s routing table only (identity-preserving:
    routing is excluded from spec equality/hash, so no recompiles and no
    plane-cache misses); already-placed history stays where it is, which
    is safe because queries sum every shard's one-sided partial. Read the
    live table back via ``.spec.routing`` — checkpoint with ``.spec`` so
    the manifest carries it.
    """

    def __init__(self, spec: SketchSpec, state: ShardedState | None = None,
                 path: str = "auto", heat_threshold: float | None = None,
                 detector: HeavyKeyDetector | None = None,
                 split_replicas: int | None = None):
        self.spec = spec
        self.path = path
        self.heat_threshold = heat_threshold
        self.detector = detector
        if detector is None and heat_threshold is not None:
            self.detector = HeavyKeyDetector()
        self.split_replicas = split_replicas
        self._state = state if state is not None else create(spec)
        self._staged = None  # (stacked EdgeBatch, n_valid) awaiting dispatch

    def _observe(self, batch: EdgeBatch) -> None:
        """Update the heavy-key summary and apply any new splits before
        this batch partitions (a key crossing the threshold re-routes
        from the current batch forward)."""
        self.detector.update(np.asarray(batch.src),
                             np.asarray(batch.src_label))
        split = {(s, l) for s, l, _ in self.spec.routing.splits} \
            if self.spec.routing else set()
        reps = self.split_replicas or self.spec.n_shards
        new = [(s, l, reps) for s, l, _ in
               self.detector.hot_keys(self.heat_threshold)
               if (s, l) not in split]
        if new:
            self.spec = self.spec.with_splits(new)

    def submit(self, batch: EdgeBatch) -> None:
        """Enqueue a time-ordered batch (partition now, dispatch on the
        next ``submit``/``flush``)."""
        if int(batch.src.shape[0]) == 0:
            return
        if self.spec.kind == "gss":
            batch = _degenerate_batch(batch)
        if self.detector is not None and self.heat_threshold is not None \
                and self.spec.n_shards > 1:
            self._observe(batch)
        self._dispatch_staged()  # async: device chews batch N ...
        self._staged = _partition_stack(self.spec, batch)  # ... host N+1

    def flush(self) -> ShardedState:
        """Dispatch any staged batch; returns the fully-applied handle."""
        self._dispatch_staged()
        return self._state

    @property
    def state(self) -> ShardedState:
        """The handle with every submitted batch applied (implicit flush)."""
        return self.flush()

    @property
    def dispatched(self) -> ShardedState:
        """The live handle with every *dispatched* batch applied — unlike
        ``state`` this does **not** flush the staged batch, so a serving
        loop can pre-warm its plane cache (``repro.sketch.query_planes``)
        without collapsing the pipeline's one-batch stagger."""
        return self._state

    @property
    def pending(self) -> int:
        """Number of staged-but-not-dispatched batches (0 or 1)."""
        return int(self._staged is not None)

    def _dispatch_staged(self) -> None:
        if self._staged is None:
            return
        stacked, n_valid = self._staged
        self._staged = None
        self._state = _dispatch_stacked(self.spec, self._state, stacked,
                                        n_valid, self.path)
