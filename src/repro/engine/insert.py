"""Single-dispatch windowed insertion for LSketch-layout states.

The seed implementation split every batch at subwindow boundaries on the
host (``np.diff`` + Python loop) and dispatched one jit call per chunk —
``O(#subwindows)`` dispatches, a fresh retrace for every new chunk length,
and a dead host-device sync per boundary. This module replaces that with a
**single jitted function per batch shape**:

  1. ``WindowRing.plan`` computes per-item segment membership (ring slot,
     structural/counter liveness) and per-slot reset flags *inside* jit;
  2. slot planes flagged for reset are zeroed up front (vectorized — the
     plan proves this commutes with the segment-by-segment replay);
  3. one ``lax.scan`` walks the time-ordered batch in stream order with the
     paper's exact first-fit probe semantics, each item writing its own
     ring slot — so a batch spanning any number of subwindows is one scan;
  4. when the batch sits in a single subwindow (the overwhelmingly common
     case for a real ingest loop) and the sketch uses uniform blocking, the
     matrix insert is routed to the block-binned path
     (``kernels/sketch_insert``: the Pallas key walk on TPU, its XLA
     lowering elsewhere) — the default on TPU; the scan path is the CPU
     default and the only path for skewed blocking or multi-subwindow
     batches.

Host entry point: ``insert_batch(cfg, state, batch, path=...)`` — pads the
batch to a size bucket (compile-count stays O(log max_batch), padding rows
are fully masked) and makes exactly one dispatch.

Equivalence contract: for any time-ordered batch the final state is
bit-identical to the legacy chunked replay (``insert_batch_chunked``) and
query-identical to the paper-literal oracle (``core/ref_prime.py``).
Property-tested in ``tests/test_engine.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashing as hsh
from repro.core.lsketch import edge_probes, insert_window_batch, precompute
from repro.core.queries import PlanesDelta
from repro.core.types import EMPTY, EdgeBatch, LSketchConfig, LSketchState
from repro.kernels import use_tpu_kernels

from .window import WindowRing, pad_to_bucket

# trace-time counters keyed by path name — tests assert single-compile
# behaviour (one trace per (cfg, batch-shape), zero traces per extra
# subwindow) by reading these before/after a workload. "fused" counts the
# single-shard entry, "stacked" the sharded [n_shards, ...] entry.
TRACE_COUNTS = {"fused": 0, "stacked": 0}


def _segment_count(widx):
    """Number of distinct contiguous subwindow segments in a sorted batch."""
    if widx.shape[0] <= 1:
        return jnp.int32(widx.shape[0])
    return jnp.int32(1) + jnp.sum((widx[1:] != widx[:-1]).astype(jnp.int32))


def _scan_insert(cfg: LSketchConfig, state: LSketchState, probes, le_idx,
                 slot, w_count, w_key, valid) -> LSketchState:
    """Stream-order first-fit insertion; per-item ring slot and liveness.

    Mirrors the paper's Algorithm 2 walk exactly (s probe cells x 2 twins,
    first key-match-or-empty wins, additional pool on miss). ``w_count``
    is the weight that survives the batch's window advances; ``w_key``
    gates structural claims (matches the per-chunk reference, where a
    chunk whose counters are later zeroed still claims keys/pool slots).
    """
    pool_slots = hsh.pool_slot_seq(
        probes.pid_src, probes.pid_dst, cfg.pool_capacity, cfg.pool_probes,
        cfg.seed)

    def body(st: LSketchState, xs):
        rows, cols, key, le, wc, wk, sl, ps, pid_s, pid_d, ok_item = xs
        # --- matrix probe: (s, 2) in paper order (probe-major, twin-minor)
        cur = st.key[rows[:, None], cols[:, None], jnp.arange(2)[None, :]]
        ok = (cur == key[:, None]) | (cur == EMPTY)
        flat = ok.reshape(-1)
        found = flat.any() & ok_item
        first = jnp.argmax(flat)
        pi, tz = first // 2, first % 2
        rr, cc = rows[pi], cols[pi]
        old = st.key[rr, cc, tz]
        new_key = st.key.at[rr, cc, tz].set(jnp.where(found, key[pi], old))
        wm = jnp.where(found, wc, 0)
        C = st.C.at[rr, cc, tz, sl].add(wm)
        P = st.P.at[rr, cc, tz, sl, le].add(wm)
        # --- pool fallback
        pk = st.pool_key[ps]
        pm = (pk[:, 0] == pid_s) & (pk[:, 1] == pid_d)
        pok = pm | (pk[:, 0] == EMPTY)
        pfound = pok.any() & ~found & (wk > 0)
        pfirst = jnp.argmax(pok)
        pslot = ps[pfirst]
        pold = st.pool_key[pslot]
        pool_key = st.pool_key.at[pslot, 0].set(
            jnp.where(pfound, pid_s, pold[0]))
        pool_key = pool_key.at[pslot, 1].set(
            jnp.where(pfound, pid_d, pold[1]))
        pw = jnp.where(pfound, wc, 0)
        pool_C = st.pool_C.at[pslot, sl].add(pw)
        pool_P = st.pool_P.at[pslot, sl, le].add(pw)
        lost = st.pool_lost + jnp.where(ok_item & ~found & ~pok.any(), wk, 0)
        return LSketchState(
            key=new_key, C=C, P=P, pool_key=pool_key, pool_C=pool_C,
            pool_P=pool_P, pool_lost=lost, slot_widx=st.slot_widx,
            cur_widx=st.cur_widx), None

    xs = (probes.rows, probes.cols, probes.keys, le_idx, w_count, w_key,
          slot, pool_slots, probes.pid_src, probes.pid_dst, valid)
    state, _ = jax.lax.scan(body, state, xs)
    return state


def insert_batch_fused_impl(cfg: LSketchConfig, state: LSketchState,
                            batch: EdgeBatch, n_valid: jax.Array,
                            use_pallas: bool = False) -> LSketchState:
    """One dispatch for a whole time-ordered batch (any #subwindows).

    ``n_valid``: traced scalar — rows >= n_valid are padding and are fully
    masked (they claim no keys, no pool slots, add no weight), so the host
    wrapper can bucket batch sizes without changing semantics.

    Plain (unjitted) so the sharded handle layer (``repro.sketch``) can
    ``vmap`` it over a stacked ``[n_shards, ...]`` state/batch axis;
    ``_insert_batch_fused`` below is the jitted single-shard entry.
    """
    TRACE_COUNTS["fused"] += 1  # trace-time side effect (compile counter)
    B = batch.src.shape[0]
    if B == 0:
        return state
    valid = jnp.arange(B, dtype=jnp.int32) < jnp.asarray(n_valid, jnp.int32)

    ring = WindowRing.for_config(cfg)
    widx = (batch.time.astype(jnp.int32)
            // jnp.int32(cfg.subwindow_size)).astype(jnp.int32)
    plan = ring.plan(state.slot_widx, state.cur_widx, widx, valid=valid)

    # apply the plan: zero re-claimed slot planes, commit ring bookkeeping
    C = WindowRing.zero_reset_slots(state.C, 3, plan.reset)
    P = WindowRing.zero_reset_slots(state.P, 3, plan.reset)
    pool_C = WindowRing.zero_reset_slots(state.pool_C, 1, plan.reset)
    pool_P = WindowRing.zero_reset_slots(state.pool_P, 1, plan.reset)
    state = LSketchState(key=state.key, C=C, P=P, pool_key=state.pool_key,
                         pool_C=pool_C, pool_P=pool_P,
                         pool_lost=state.pool_lost,
                         slot_widx=plan.slot_widx, cur_widx=plan.cur_widx)

    pa = precompute(cfg, batch.src, batch.src_label)
    pb = precompute(cfg, batch.dst, batch.dst_label)
    probes = edge_probes(cfg, pa, pb)
    le_idx = hsh.edge_label_bucket(batch.edge_label, cfg.c, cfg.seed)
    w = batch.weight.astype(state.C.dtype)
    w_count = w * plan.count_live.astype(w.dtype)
    w_key = w * plan.key_live.astype(w.dtype)

    def scan_path(st):
        return _scan_insert(cfg, st, probes, le_idx, plan.slot, w_count,
                            w_key, valid)

    if not use_pallas:
        return scan_path(state)

    # Pallas fast path: eligible iff the (valid prefix of the) batch sits in
    # one subwindow — then every item shares plan.slot[0] and
    # count_live == key_live, which is exactly the kernel's contract.
    from repro.kernels.sketch_insert.ops import matrix_insert_binned

    def pallas_path(st):
        return matrix_insert_binned(cfg, st, probes, le_idx, w_count,
                                    plan.slot[0], max_bin=B)

    one_segment = _segment_count(
        jnp.where(valid, widx, widx[0])) == jnp.int32(1)
    return jax.lax.cond(one_segment, pallas_path, scan_path, state)


_insert_batch_fused = functools.partial(
    jax.jit, static_argnums=(0,), static_argnames=("use_pallas",),
    donate_argnums=1)(insert_batch_fused_impl)


# --------------------------------------------------------------------------
# stacked (shard-axis) insertion — the repro.sketch ingest backend
# --------------------------------------------------------------------------

def _touched_slot_slices(states: LSketchState, slot):
    """Per-shard counter slices at ring slot ``slot`` (int32 [S]) — the
    only slot a single-segment flush writes. C/P slice on the slot axis
    (axis 4 of [S, d, d, 2, k(, c)]), pool planes on axis 2."""
    sl = slot.astype(jnp.int32)
    c = jnp.take_along_axis(
        states.C, sl[:, None, None, None, None], axis=4)[..., 0]
    p = jnp.take_along_axis(
        states.P, sl[:, None, None, None, None, None], axis=4)[..., 0, :]
    pc = jnp.take_along_axis(states.pool_C, sl[:, None, None], axis=2)[..., 0]
    pp = jnp.take_along_axis(
        states.pool_P, sl[:, None, None, None], axis=2)[..., 0, :]
    return c, p, pc, pp


def insert_stacked_fused_impl(cfg: LSketchConfig, states: LSketchState,
                              batch: EdgeBatch, n_valid: jax.Array,
                              use_pallas: bool = False,
                              emit_delta: bool = False):
    """One dispatch for a whole ``[n_shards, B]`` hash-partitioned batch.

    ``states``/``batch`` carry a leading ``[n_shards]`` axis on every leaf;
    ``n_valid`` is int32 [n_shards] (rows >= n_valid[s] are shard ``s``'s
    padding — fully masked, including ring bookkeeping, so an empty shard
    is a strict no-op).

    Path choice mirrors the single-shard fused path, lifted to the stack:
    the ring plan and addressing are computed for all shards vectorized;
    when **every** shard's valid prefix sits in a single subwindow (the
    overwhelmingly common serving case — and always true for GSS) the
    matrix insert is one shard-axis Pallas launch
    (``matrix_insert_binned_sharded``, grid (n_shards, n_blocks,
    n_blocks)); otherwise a vmapped ``lax.scan`` replays each shard in
    stream order. Both live under one ``lax.cond`` in one jitted dispatch.

    With ``emit_delta`` (static) the return value is ``(states, delta)``
    where ``delta`` is the ``core.queries.PlanesDelta`` of this flush —
    the touched-slot counter increments, sliced inside this dispatch
    because the caller's input buffers are donated (there is no "before"
    to diff against once we return). ``delta.ok`` is recorded **per shard
    row** (tenant-axis dispatch, DESIGN.md §11): row ``s`` is False when
    that row's flush spanned several subwindows or reset one of its ring
    slots — its slices are then meaningless. The query layer ANDs the
    rows whose window reconciliation couples them (all rows for a plain
    sharded handle, each tenant's row group for a pooled one) before
    applying; a failed group rebuilds planes cold (DESIGN.md §10).

    Semantics are bit-identical to vmapping ``insert_batch_fused_impl``
    over the shard axis (property-tested in tests/test_sketch_api.py).
    """
    TRACE_COUNTS["stacked"] += 1  # trace-time side effect (compile counter)
    # named scopes (``ingest.*``) tag every instruction's op_name metadata,
    # so a device trace can put each operation on the step that made it;
    # they add metadata only, never an instruction
    S, B = batch.src.shape
    with jax.named_scope("ingest.plan"):
        valid = jnp.arange(B, dtype=jnp.int32)[None, :] \
            < jnp.asarray(n_valid, jnp.int32)[:, None]

        ring = WindowRing.for_config(cfg)
        widx = (batch.time.astype(jnp.int32)
                // jnp.int32(cfg.subwindow_size)).astype(jnp.int32)
        plan = jax.vmap(ring.plan)(states.slot_widx, states.cur_widx, widx,
                                   valid)

    # apply the plan per shard: zero re-claimed slot planes, commit ring
    with jax.named_scope("ingest.reclaim"):
        zero = lambda arr, axis: jax.vmap(
            lambda a, r: WindowRing.zero_reset_slots(a, axis, r))(
                arr, plan.reset)
        states = LSketchState(
            key=states.key, C=zero(states.C, 3), P=zero(states.P, 3),
            pool_key=states.pool_key, pool_C=zero(states.pool_C, 1),
            pool_P=zero(states.pool_P, 1), pool_lost=states.pool_lost,
            slot_widx=plan.slot_widx, cur_widx=plan.cur_widx)

    with jax.named_scope("ingest.plan"):
        # addressing is vectorized over any batch shape — feed [S, B]
        pa = precompute(cfg, batch.src, batch.src_label)
        pb = precompute(cfg, batch.dst, batch.dst_label)
        probes = edge_probes(cfg, pa, pb)
        le_idx = hsh.edge_label_bucket(batch.edge_label, cfg.c, cfg.seed)
        w = batch.weight.astype(states.C.dtype)
        w_count = w * plan.count_live.astype(w.dtype)
        w_key = w * plan.key_live.astype(w.dtype)

    def scan_path(st):
        with jax.named_scope("ingest.scan"):
            return jax.vmap(
                lambda s_st, s_pr, s_le, s_sl, s_wc, s_wk, s_v: _scan_insert(
                    cfg, s_st, s_pr, s_le, s_sl, s_wc, s_wk, s_v)
            )(st, probes, le_idx, plan.slot, w_count, w_key, valid)

    # single-segment test: every shard's valid prefix is one subwindow.
    # Gates the sharded kernel (each shard's items then share
    # plan.slot[s, 0] and count_live == key_live — the kernel's contract,
    # shard by shard) and the delta record (all writes land in one slot).
    if use_pallas or emit_delta:
        with jax.named_scope("ingest.plan"):
            one_segment_rows = jax.vmap(
                lambda wdx, v: _segment_count(jnp.where(v, wdx, wdx[0])))(
                    widx, valid) == jnp.int32(1)
            one_segment_all = jnp.all(one_segment_rows)

    touched = plan.slot[:, 0]
    if emit_delta:
        with jax.named_scope("ingest.delta"):
            pre = _touched_slot_slices(states, touched)

    if not use_pallas:
        out = scan_path(states)
    else:
        from repro.kernels.sketch_insert.ops import \
            matrix_insert_binned_sharded

        def pallas_path(st):
            with jax.named_scope("ingest.walk"):
                return matrix_insert_binned_sharded(
                    cfg, st, probes, le_idx, w_count, touched, max_bin=B)

        out = jax.lax.cond(one_segment_all, pallas_path, scan_path, states)

    if not emit_delta:
        return out
    with jax.named_scope("ingest.delta"):
        post = _touched_slot_slices(out, touched)
        # per row: no reset <=> that row's ring is unchanged (a cur_widx
        # advance implies a reset), so its every-horizon validity mask is
        # unchanged and its slot increment is the exact planes delta. The
        # AND over window-coupled rows is the caller's (tenant groups
        # differ)
        ok = one_segment_rows & ~jnp.any(plan.reset, axis=1)
        delta = PlanesDelta(
            ok=ok, slot=touched, d_c=post[0] - pre[0], d_p=post[1] - pre[1],
            d_pool_c=post[2] - pre[2], d_pool_p=post[3] - pre[3])
    return out, delta


# (the stacked impl is jitted by its one frontend, repro.sketch.ingest —
# jitting here too would just duplicate the cache entry)


# --------------------------------------------------------------------------
# host frontends
# --------------------------------------------------------------------------

def default_path(cfg: LSketchConfig) -> str:
    """The binned path (Pallas key walk on TPU) is the default
    matrix-insert path on TPU for uniform blocking; the fused scan is the
    default elsewhere and the one path for skewed blocking (the binned
    walk needs equal tiles)."""
    if use_tpu_kernels() and cfg.block_bounds is None:
        return "pallas"
    return "scan"


def resolve_path(cfg: LSketchConfig, path: str = "auto") -> str:
    """Normalize a user-facing path name to "scan" | "pallas" | "chunked".

    The one path-selection rule (shared by the single-shard and stacked
    frontends): "auto" is ``default_path(cfg)``; an explicit "pallas" on
    a skewed-blocking config raises (the kernel needs uniform tiles).
    """
    if path == "auto":
        path = default_path(cfg)
    if path == "pallas" and cfg.block_bounds is not None:
        raise ValueError("path='pallas' needs uniform blocking; skewed "
                         "block_bounds take path='scan' (or 'auto')")
    if path not in ("scan", "pallas", "chunked"):
        raise ValueError(f"unknown insert path {path!r}")
    return path


def insert_batch(cfg: LSketchConfig, state: LSketchState, batch: EdgeBatch,
                 path: str = "auto", bucket: bool = True) -> LSketchState:
    """Insert a time-ordered batch in **one** jit dispatch.

    path: "auto" (backend default), "scan" (fused lax.scan), "pallas"
    (fused + block-binned kernel for single-subwindow batches; requires
    uniform blocking), or "chunked" (legacy host split loop — reference).
    """
    n = int(batch.src.shape[0])
    if n == 0:
        return state
    path = resolve_path(cfg, path)
    if path == "chunked":
        return insert_batch_chunked(cfg, state, batch)
    padded = jax.tree.map(pad_to_bucket, batch) if bucket else batch
    return _insert_batch_fused(cfg, state, padded, jnp.int32(n),
                               use_pallas=path == "pallas")


def insert_batch_chunked(cfg: LSketchConfig, state: LSketchState,
                         batch: EdgeBatch) -> LSketchState:
    """Legacy host-side chunk loop (one dispatch per subwindow boundary).

    Kept as the sequential reference the fused path is tested against and
    as the last-resort fallback; new code should call ``insert_batch``.
    """
    t = np.asarray(batch.time)
    if t.shape[0] == 0:
        return state
    widx = t // cfg.subwindow_size
    cuts = np.flatnonzero(np.diff(widx)) + 1
    starts = np.concatenate([[0], cuts])
    ends = np.concatenate([cuts, [len(t)]])
    for a, b in zip(starts, ends):
        chunk = jax.tree.map(lambda x: x[a:b], batch)
        state = insert_window_batch(cfg, state, chunk, int(widx[a]))
    return state
