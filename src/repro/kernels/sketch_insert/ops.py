"""Public wrappers for the block-binned insertion kernel.

Pipeline (DESIGN.md §2 "binned batch insertion"):
  1. advance the sliding window (``engine.WindowRing`` claim/zero — or the
     fused segment plan when called from ``engine.insert``);
  2. vectorized addressing: probes, keys, block ids for the whole batch;
  3. stable binning by destination block (order within a block == stream
     order, so first-fit semantics match the sequential algorithm exactly);
  4. the first-fit key walk per block: the Pallas kernel on TPU, its
     compiled XLA lowering elsewhere (``repro.kernels.use_tpu_kernels``);
  5. one scatter-add of every landed edge's weight into ``C``/``P``;
  6. additional-pool pass for the (rare) all-probes-occupied edges, in
     original stream order.

``matrix_insert_binned_sharded`` is the composable middle: pre-addressed
probes plus each edge's target ring slot, over a ``[n_shards, ...]``
stack; the engine's stacked insert (the ``repro.sketch`` handle layer)
routes through it. The walk never reads a slot (``key`` has no slot
axis): the slot only picks where steps 5 and 6 add each edge's weight,
so one launch serves a batch that spans several subwindows.
``matrix_insert_binned`` is its one-shard case and
``insert_window_batch_pallas`` the standalone per-subwindow drop-in kept
for tests and direct use.

Restrictions: uniform blocking only (equal tiles — skewed blocking takes
the engine's scan path, ``engine.insert.resolve_path``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import hashing as hsh
from repro.core.lsketch import (EdgeProbes, advance_window, edge_probes,
                                precompute)
from repro.core.types import EdgeBatch, LSketchConfig, LSketchState

from repro.kernels import use_tpu_kernels

from .kernel import CHUNK, sketch_insert_stream_walk, sketch_insert_walk_kernel


def _pool_pass(cfg: LSketchConfig, state: LSketchState, slot, probes, le_idx,
               weight, failed) -> LSketchState:
    """Additional-pool insertion for edges the matrix rejected (stream order),
    each at its own ring slot (``slot`` [B]).

    The walk visits only the failed items: a stable sort puts them first
    (stream order preserved among them — non-failed items are provable
    no-ops, so skipping them is bit-identical) and a ``while_loop`` stops
    after the last one. Pool overflow is the rare path, so this is O(few)
    instead of O(batch).
    """
    pool_slots = hsh.pool_slot_seq(probes.pid_src, probes.pid_dst,
                                   cfg.pool_capacity, cfg.pool_probes, cfg.seed)
    order = jnp.argsort(~failed, stable=True)  # failed first, stream order
    n_failed = jnp.sum(failed.astype(jnp.int32))

    def body(carry):
        idx, st = carry
        i = order[idx]
        w = jnp.where(failed[i], weight[i], 0)
        ps = pool_slots[i]
        pk = st.pool_key[ps]
        pmatch = (pk[:, 0] == probes.pid_src[i]) & (pk[:, 1] == probes.pid_dst[i])
        pok = pmatch | (pk[:, 0] == jnp.int32(-1))
        pfound = pok.any() & (w > 0)
        pfirst = jnp.argmax(pok)
        pslot = ps[pfirst]
        pold = st.pool_key[pslot]
        pool_key = st.pool_key.at[pslot, 0].set(
            jnp.where(pfound, probes.pid_src[i], pold[0]))
        pool_key = pool_key.at[pslot, 1].set(
            jnp.where(pfound, probes.pid_dst[i], pold[1]))
        pw = jnp.where(pfound, w, 0)
        pool_C = st.pool_C.at[pslot, slot[i]].add(pw)
        pool_P = st.pool_P.at[pslot, slot[i], le_idx[i]].add(pw)
        lost = st.pool_lost + jnp.where((w > 0) & ~pok.any(), w, 0)
        return idx + 1, LSketchState(
            key=st.key, C=st.C, P=st.P, pool_key=pool_key,
            pool_C=pool_C, pool_P=pool_P, pool_lost=lost,
            slot_widx=st.slot_widx, cur_widx=st.cur_widx)

    _, state = jax.lax.while_loop(lambda c: c[0] < n_failed, body,
                                  (jnp.int32(0), state))
    return state


def _bin_plan(cfg: LSketchConfig, probes: EdgeProbes, weight):
    """The one stable binning rule every lowering shares: per-edge block
    id (uniform tiles: block = row // b; all ``s`` probes of an edge stay
    in one block, so probe 0 decides), sort order, per-bin fills and
    start offsets.

    Zero-weight rows (bucket padding, expired items) are no-ops in the
    matrix walk — they are routed to a virtual one-past-last bin so they
    never occupy bin slots (replicate-last padding would otherwise pile a
    whole row's padding into one real bin and stretch the walk by its
    length). Returns ``(bid0, bid, order, counts, offs)`` where ``bid0``
    is the raw (unrouted) block id. One shard (1-D); vmap over a leading
    shard axis."""
    n, b = cfg.n_blocks, cfg.b
    bid0 = (probes.rows[:, 0] // jnp.int32(b)) * jnp.int32(n) \
        + (probes.cols[:, 0] // jnp.int32(b))
    bid = jnp.where(weight > 0, bid0, jnp.int32(n * n))
    order = jnp.argsort(bid, stable=True)
    counts = jnp.bincount(bid, length=n * n)  # OOB (dead) rows drop out
    offs = jnp.cumsum(counts) - counts
    return bid0, bid, order, counts, offs


def _key_walk_xla(cfg: LSketchConfig, probes: EdgeProbes, weight, key_t,
                  max_bin: int):
    """First-fit key walk, compiled XLA lowering. ``probes``/``weight``
    carry a leading shard axis; ``key_t`` is ``[S, 2, d, d]``. Returns
    ``(new_key_t, enc [S, B] in stream order, bid0 [S, B])``."""
    n, b = cfg.n_blocks, cfg.b
    bid0, _, order, counts, offs = jax.vmap(
        lambda p, w: _bin_plan(cfg, p, w))(probes, weight)
    new_key_t, enc = sketch_insert_stream_walk(
        probes.rows % jnp.int32(b), probes.cols % jnp.int32(b),
        probes.keys, weight, order, offs, counts, key_t,
        n_shards=weight.shape[0], n_blocks=n, b=b, max_bin=max_bin)
    return new_key_t, enc, bid0


def _key_walk_pallas(cfg: LSketchConfig, probes: EdgeProbes, weight, key_t,
                     max_bin: int, interpret: bool = False):
    """First-fit key walk on the Pallas kernel: the same contract as
    ``_key_walk_xla``, bit-identical. Materializes the bins the kernel's
    BlockSpecs read (``[S, n^2, max_bin, 2s + 1]``, one SMEM row per
    binned edge) and un-bins ``enc`` back to stream order.
    ``interpret`` is for the CPU parity tests only."""
    n, b, s = cfg.n_blocks, cfg.b, cfg.s
    n2 = n * n
    cap = -(-max_bin // CHUNK) * CHUNK

    def bin_one(p, w):
        bid0, bid, order, counts, offs = _bin_plan(cfg, p, w)
        bid_s = bid[order]
        pos = jnp.arange(w.shape[0], dtype=jnp.int32) \
            - offs[jnp.minimum(bid_s, n2 - 1)].astype(jnp.int32)
        row = jnp.concatenate([(p.rows % b) * b + p.cols % b, p.keys,
                               w[:, None]], axis=1)  # [B, 2s + 1]
        bins = jnp.zeros((n2, cap, 2 * s + 1), jnp.int32).at[
            bid_s, pos].set(row[order], mode="drop")
        fill = jnp.minimum(counts, max_bin).astype(jnp.int32)
        return bins, fill, (bid0, order, bid_s, pos)

    bins, fill, (bid0, order, bid_s, pos) = jax.vmap(bin_one)(probes, weight)
    new_key_t, enc_b = sketch_insert_walk_kernel(
        bins, fill, key_t, n_blocks=n, b=b, s=s, interpret=interpret)

    def unbin(e, o, bs, ps):
        walked = (bs < n2) & (ps < max_bin)
        got = e[jnp.minimum(bs, n2 - 1), jnp.clip(ps, 0, cap - 1)]
        return jnp.zeros_like(o).at[o].set(jnp.where(walked, got, 0))

    return new_key_t, jax.vmap(unbin)(enc_b, order, bid_s, pos), bid0


def matrix_insert_binned(cfg: LSketchConfig, state: LSketchState,
                         probes: EdgeProbes, le_idx, weight, slot,
                         max_bin: int | None = None) -> LSketchState:
    """Block-binned insertion of one shard's pre-addressed batch: the
    one-shard case of ``matrix_insert_binned_sharded``.

    Traced (not jitted) — compose inside a jitted caller. ``weight`` must
    already carry the window-liveness mask (zeros insert nothing and claim
    nothing); ``slot`` is each item's (traced) ring slot, ``[B]``, or one
    scalar shared by the whole batch.
    """
    lift = lambda x: x[None]
    out = matrix_insert_binned_sharded(
        cfg, jax.tree.map(lift, state), jax.tree.map(lift, probes),
        le_idx[None], weight[None], jnp.broadcast_to(slot, weight.shape)[None],
        max_bin=max_bin)
    return jax.tree.map(lambda x: x[0], out)


def matrix_insert_binned_sharded(cfg: LSketchConfig, state: LSketchState,
                                 probes: EdgeProbes, le_idx, weight, slot,
                                 max_bin: int | None = None) -> LSketchState:
    """Block-binned insertion over a whole ``[n_shards, ...]`` stack.

    ``state`` carries a leading ``[n_shards]`` axis on every leaf; probe
    tensors are ``[n_shards, B, s]``; ``le_idx``, ``weight`` and ``slot``
    are ``[n_shards, B]``, ``slot`` each edge's own (traced) ring slot.
    ``weight`` must already carry the per-shard window-liveness **and**
    ``n_valid`` padding mask (zero-weight rows insert nothing and claim
    nothing — an all-zero row is how an empty shard stays a strict
    no-op). Traced (not jitted) — compose inside a
    jitted caller.

    The key walk runs on the Pallas kernel on TPU and on its compiled XLA
    lowering elsewhere; either way the counters (write-only in the walk)
    land in one scatter-add on the full stacked ``C``/``P``, each edge at
    its own slot, with no per-slot plane gather or write-back. The result
    equals the stream-order scan whenever every positive-weight edge's
    counters survive the batch (``count_live == key_live``): claims do not
    depend on the slot.
    """
    if cfg.block_bounds is not None:
        raise ValueError("the binned insert supports uniform blocking only")
    S, B = probes.rows.shape[:2]
    max_bin = B if max_bin is None else max_bin
    n, b = cfg.n_blocks, cfg.b

    walk = _key_walk_pallas if use_tpu_kernels() else _key_walk_xla
    new_key_t, enc, bid0 = walk(cfg, probes, weight,
                                jnp.moveaxis(state.key, 3, 1), max_bin)
    inserted = enc > 0  # [S, B], stream order
    v = jnp.maximum(enc - 1, 0)
    tzs = v // (b * b)
    rs = (bid0 // jnp.int32(n)) * jnp.int32(b) + (v // b) % b
    cs = (bid0 % jnp.int32(n)) * jnp.int32(b) + v % b
    wm = jnp.where(inserted, weight, 0)
    s_idx = jnp.arange(S, dtype=jnp.int32)[:, None]
    state = LSketchState(key=jnp.moveaxis(new_key_t, 1, 3),
                         C=state.C.at[s_idx, rs, cs, tzs, slot].add(wm),
                         P=state.P.at[s_idx, rs, cs, tzs, slot,
                                      le_idx].add(wm),
                         pool_key=state.pool_key,
                         pool_C=state.pool_C, pool_P=state.pool_P,
                         pool_lost=state.pool_lost,
                         slot_widx=state.slot_widx, cur_widx=state.cur_widx)

    # --- vmapped stream-order pool pass over the matrix rejects ---
    failed = (~inserted) & (weight > 0)
    return jax.vmap(
        lambda st, sl, pr, le, w, fl: _pool_pass(cfg, st, sl, pr, le, w, fl)
    )(state, slot, probes, le_idx, weight, failed)


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=("max_bin",),
                   donate_argnums=1)
def insert_window_batch_pallas(cfg: LSketchConfig, state: LSketchState,
                               batch: EdgeBatch, widx,
                               max_bin: int | None = None) -> LSketchState:
    """Drop-in replacement for ``repro.core.insert_window_batch``."""
    pa = precompute(cfg, batch.src, batch.src_label)
    pb = precompute(cfg, batch.dst, batch.dst_label)
    probes = edge_probes(cfg, pa, pb)
    le_idx = hsh.edge_label_bucket(batch.edge_label, cfg.c, cfg.seed)
    state, slot, live = advance_window(cfg, state, jnp.asarray(widx, jnp.int32))
    weight = batch.weight.astype(state.C.dtype) * live.astype(state.C.dtype)
    return matrix_insert_binned(cfg, state, probes, le_idx, weight, slot,
                                max_bin=max_bin)
