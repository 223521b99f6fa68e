"""Pallas kernel: the first-fit key walk of block-binned LSketch insertion.

The paper's hot loop (Algorithm 2, lines 10-23) splits into a sequential
half and a parallel half. First-fit reads and writes only ``key``: each
edge claims the first of its ``s`` probe cells x 2 twins whose key
matches or is empty, in stream order. The ``C``/``P`` counters are
write-only adds at the landing cell. So the kernel walks ``key`` alone
and returns every edge's landing cell; the caller applies all counter
weight in one scatter-add (``ops.matrix_insert_binned_sharded``), and
the ``[d, d, 2, k, c]`` counter planes never enter VMEM.

TPU mapping (``sketch_insert_walk_kernel``):

  * grid ``(n_shards, n_blocks, n_blocks, n_chunks)``: one storage block
    (mA, mB) per (shard, i, j). All ``s`` probes of an edge fall inside
    one block (the paper's Storage Blocks Division), so blocks never
    share a cell and only the walk inside a block is sequential. The
    chunk axis streams that block's bin through in ``CHUNK``-edge pieces
    and is the one sequential ("arbitrary") grid axis.
  * the block's key tile, laid out ``(2, b // 8, 8, b)``, stays in VMEM
    across the chunk axis (its block index does not move). A probe reads
    the (8, b) row group holding its row, a dynamic index on an untiled
    leading axis, and selects the cell by an iota mask: no dynamic index
    ever lands on the two tiled minor dims.
  * the bins travel through SMEM, since the walk needs them as scalars:
    per edge ``2s + 1`` words (``s`` packed tile-relative cells
    ``r * b + c``, ``s`` keys, the weight), chunked ``(rows, 128)`` so
    the SMEM blocks satisfy the (8, 128) block rule. Per-bin fills come
    in by scalar prefetch, so the walk stops at each bin's last edge.
  * ``enc`` (per binned edge: 0 = not inserted, else ``1 + (tz * b + r)
    * b + c``) goes out through SMEM as well.

VMEM per grid step (b=512): the key tile is 2 MiB, in and out, double
buffered: 8 MiB against the raised 48 MiB scoped limit.

``sketch_insert_stream_walk`` is the compiled XLA lowering of the same
walk, used where the Pallas kernel cannot compile (every backend but
TPU); tests hold the kernel (in interpret mode) bit-identical to it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

EMPTY = -1
CHUNK = 128  # edges per SMEM chunk: (2s + 1) * CHUNK words is a 128-multiple
VMEM_LIMIT = 48 * 1024 * 1024


def _walk_body(counts_ref, bins_ref, key_in, key_ref, enc_ref, *, s: int,
               b: int, n_blocks: int):
    """One chunk of one block's bin, walked in stream order."""
    h, i, j, t = (pl.program_id(a) for a in range(4))

    @pl.when(t == 0)
    def _():  # the out tile starts as the block's input tile
        key_ref[...] = key_in[...]

    fill = jnp.clip(counts_ref[h, i * n_blocks + j] - t * CHUNK, 0, CHUNK)
    sub = jax.lax.broadcasted_iota(jnp.int32, (8, b), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (8, b), 1)
    width = 2 * s + 1

    def word(q):
        return bins_ref[0, 0, 0, q // 128, q % 128]

    def edge(e, carry):
        base = e * width
        found = jnp.bool_(False)
        tz_sel = jnp.int32(0)
        cell_sel = jnp.int32(0)
        key_sel = jnp.int32(0)
        # the s*2 candidates in paper order (probe-major, twin-minor)
        for p in range(s):
            cell = word(base + p)
            kw = word(base + s + p)
            r, c = cell // b, cell % b
            at = (sub == r % 8) & (lane == c)
            for tz in range(2):
                cur = jnp.sum(jnp.where(at, key_ref[0, tz, r // 8], 0))
                ok = ((cur == kw) | (cur == EMPTY)) & ~found
                tz_sel = jnp.where(ok, tz, tz_sel)
                cell_sel = jnp.where(ok, cell, cell_sel)
                key_sel = jnp.where(ok, kw, key_sel)
                found = found | ok
        found = found & (word(base + 2 * s) > 0)
        r, c = cell_sel // b, cell_sel % b

        @pl.when(found)
        def _():
            rows8 = key_ref[0, tz_sel, r // 8]
            key_ref[0, tz_sel, r // 8] = jnp.where(
                (sub == r % 8) & (lane == c), key_sel, rows8)

        enc_ref[0, 0, 0, 0, e] = jnp.where(found,
                                           1 + tz_sel * (b * b) + cell_sel, 0)
        return carry

    jax.lax.fori_loop(0, fill, edge, 0)


@functools.partial(jax.jit, static_argnames=("n_blocks", "b", "s",
                                             "interpret"))
def sketch_insert_walk_kernel(bins, counts, key, *, n_blocks: int, b: int,
                              s: int, interpret: bool = False):
    """First-fit key walk over every shard's every storage block.

    bins: int32 ``[S, n^2, max_bin, 2s + 1]`` per binned edge: ``s``
    tile-relative cells ``r * b + c``, ``s`` keys, the weight (zero-padded
    past each bin's fill); ``max_bin`` a multiple of ``CHUNK``.
    counts: int32 ``[S, n^2]`` bin fills. key: ``[S, 2, d, d]``
    twin-leading keys (updated in place under donation).

    Returns ``(new_key [S, 2, d, d], enc [S, n^2, max_bin])``.
    """
    S, n2, max_bin, width = bins.shape
    if width != 2 * s + 1 or max_bin % CHUNK:
        raise ValueError("bins must be [S, n^2, k * CHUNK, 2s + 1]")
    nch = max_bin // CHUNK
    d = n_blocks * b
    rows = CHUNK * width // 128

    tile = pl.BlockSpec((1, 2, b // 8, 8, b),
                        lambda h, i, j, t, cnt: (h, 0, i, 0, j))
    chunk = lambda h, i, j, t, cnt: (h, i * n_blocks + j, t, 0, 0)
    bin_spec = pl.BlockSpec((1, 1, 1, rows, 128), chunk,
                            memory_space=pltpu.SMEM)
    enc_spec = pl.BlockSpec((1, 1, 1, 1, CHUNK), chunk,
                            memory_space=pltpu.SMEM)
    key5 = key.reshape(S, 2, d // 8, 8, d)
    new_key, enc = pl.pallas_call(
        functools.partial(_walk_body, s=s, b=b, n_blocks=n_blocks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S, n_blocks, n_blocks, nch),
            in_specs=[bin_spec, tile], out_specs=[tile, enc_spec]),
        out_shape=[jax.ShapeDtypeStruct(key5.shape, key5.dtype),
                   jax.ShapeDtypeStruct((S, n2, nch, 1, CHUNK), jnp.int32)],
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="sketch_insert_walk",
    )(counts, bins.reshape(S, n2, nch, rows, 128), key5)
    return new_key.reshape(S, 2, d, d), enc.reshape(S, n2, max_bin)


def sketch_insert_stream_walk(rows, cols, keys, w, order, offs, counts,
                              key, *, n_shards: int, n_blocks: int, b: int,
                              max_bin: int | None = None):
    """XLA lowering of ``sketch_insert_walk_kernel``: walk the key tiles
    in bin order and *collect* each edge's landing cell.

    One step of the ``while_loop`` processes edge ``t`` of every
    (shard, block) bin at once, read straight out of the bin-sorted
    stream (a gather at ``offs + t``), so the ``[n2, max_bin, ...]`` bin
    tensors the kernel's BlockSpecs need are never built here.

    Inputs: ``rows``/``cols`` ([S, B, s], **tile-relative** probe coords,
    stream order), ``keys`` [S, B, s], ``w`` [S, B] (weights already
    carrying every mask — zero weight neither claims nor counts),
    ``order`` [S, B] (stable bin sort), ``offs``/``counts`` [S, n2] (bin
    start/fill within each shard's sorted stream), ``key`` [S, 2, d, d].

    Returns ``(new_key [S, 2, d, d], enc [S, B])`` where ``enc`` is per
    item **in stream order**: 0 = not inserted (pool candidate iff its
    weight is positive), else ``1 + (tz * b + r_rel) * b + c_rel`` — the
    landing cell, packed. Walk length is ``min(max(counts), max_bin)``:
    the true largest bin fill, not the padded batch length — and capped
    at ``max_bin`` so a tuned bin capacity drops each bin's overflow
    edges to the pool exactly like the kernel's truncated bins (an
    un-walked edge keeps ``enc == 0``). Traced (not jitted) —
    compose inside a jitted caller.
    """
    S, n2 = n_shards, n_blocks * n_blocks
    B = w.shape[1]
    NB = S * n2
    nb_idx = jnp.arange(NB, dtype=jnp.int32)
    limit = jnp.max(counts)
    if max_bin is not None:
        limit = jnp.minimum(limit, jnp.int32(max_bin))

    def flat_sorted(x):  # [S, B, ...] -> bin-sorted, shard-flattened
        idx = order if x.ndim == 2 else order[..., None]
        return jnp.take_along_axis(x, idx, axis=1).reshape((S * B,)
                                                           + x.shape[2:])

    rows_s, cols_s, keys_s, w_s = (flat_sorted(v)
                                   for v in (rows, cols, keys, w))
    # global sorted position of bin nb's first edge
    base = (nb_idx // n2) * jnp.int32(B) + offs.reshape(NB)
    counts_f = counts.reshape(NB)

    def body(state):
        t, key_t, enc_s = state
        live = t < counts_f  # [NB]
        gi = jnp.where(live, base + t, jnp.int32(S * B))  # OOB -> clamp/drop
        r = rows_s[jnp.minimum(gi, S * B - 1)]  # [NB, s]
        cc = cols_s[jnp.minimum(gi, S * B - 1)]
        kk = keys_s[jnp.minimum(gi, S * B - 1)]
        w_t = jnp.where(live, w_s[jnp.minimum(gi, S * B - 1)], 0)
        cur = key_t[nb_idx[:, None, None], jnp.arange(2)[None, None, :],
                    r[:, :, None], cc[:, :, None]]  # [NB, s, 2]
        ok = ((cur == kk[:, :, None]) | (cur == EMPTY)).reshape(NB, -1)
        found = ok.any(axis=1) & (w_t > 0)
        first = jnp.argmax(ok, axis=1)
        pi, tz = first // 2, first % 2
        take = lambda a: jnp.take_along_axis(a, pi[:, None], axis=1)[:, 0]
        r_sel, c_sel, k_sel = take(r), take(cc), take(kk)
        old = key_t[nb_idx, tz, r_sel, c_sel]
        key_t = key_t.at[nb_idx, tz, r_sel, c_sel].set(
            jnp.where(found, k_sel, old))
        # packed collect write: 0 = not inserted, else 1 + cell id
        enc = jnp.where(found, 1 + (tz * b + r_sel) * b + c_sel, 0)
        return t + 1, key_t, enc_s.at[gi].set(enc, mode="drop")

    key_t = jnp.moveaxis(key.reshape(S, 2, n_blocks, b, n_blocks, b),
                         (2, 4), (1, 2)).reshape(NB, 2, b, b)
    state = (jnp.int32(0), key_t, jnp.zeros((S * B,), jnp.int32))
    _, key_t, enc_s = jax.lax.while_loop(lambda st: st[0] < limit, body,
                                         state)

    x = key_t.reshape(S, n_blocks, n_blocks, 2, b, b)
    new_key = jnp.moveaxis(x, (1, 2), (2, 4)).reshape(S, 2, n_blocks * b,
                                                      n_blocks * b)
    # un-sort the collect array back to stream order
    enc = jnp.zeros((S, B), jnp.int32).at[
        jnp.arange(S, dtype=jnp.int32)[:, None], order].set(
            enc_s.reshape(S, B))
    return new_key, enc
