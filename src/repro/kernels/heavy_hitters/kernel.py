"""Pallas kernel: cell-owner decode for the heavy-hitter portfolio.

The reversible-sketch trick (gMatrix, arXiv 1510.02219): every occupied
cell's stored key carries (candidate index, fingerprint) for both
endpoints, so the packed vertex identities of the cell's source and
destination are recoverable in closed form — no raw-id table. The kernel
decodes all ``2 * d * d`` cells of a shard's window-reduced planes in one
VPU pass: unpack the key fields, replay the ``r``-step LCG candidate
chain (static unroll, select at the stored index), invert the modular
address, pack ``(block, address, fingerprint)``. The top-k aggregation
over the decoded owners is matmul/sort-shaped and stays in XLA
(``ops.segment_topk``); the per-cell integer decode is the kernelizable
middle.

Grid = (shards, row bands): one ``(2, ROWS, d)`` band of one shard's
key plane per step, so VMEM holds a few MiB at any width (a whole
``d = 1024`` plane plus its two outputs and the decode temporaries would
not fit the scoped limit). ``cell_decode_xla`` is the compiled pure-XLA
twin (the route on every backend but TPU) built on
``hashing.decode_line_vid``, the same shared reversibility seam
``reshard``/BFS/host-analytics use; results are bit-identical (integer
ops only).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

EMPTY = -1
IDX_RADIX = 16
# LCG family constants — must mirror repro.core.hashing (bit-parity)
LCG_T = 1103515245
LCG_I = 12345
M_MASK = 0x7FFFFFFF


def _chain_select(f, idx, r: int):
    """offs(f)[idx]: the idx-th entry of the LCG candidate chain seeded by
    fingerprint f — static unroll with a where-select, elementwise over
    any shape (the in-kernel twin of ``hashing.candidate_offsets`` +
    ``take_along_axis``)."""
    t = jnp.uint32(LCG_T)
    inc = jnp.uint32(LCG_I)
    mask = jnp.uint32(M_MASK)
    x = (t * f.astype(jnp.uint32) + inc) & mask
    sel = jnp.zeros_like(f)
    for i in range(r):
        sel = jnp.where(idx == i, x.astype(jnp.int32), sel)
        x = (t * x + inc) & mask
    return sel


def _block_lookup(line, starts, widths):
    """(start, width) of the label block containing an absolute line index
    — static unroll over the (ascending) block partition."""
    start = jnp.full_like(line, starts[0])
    width = jnp.full_like(line, widths[0])
    blk = jnp.zeros_like(line)
    for b in range(1, len(starts)):
        ge = line >= starts[b]
        start = jnp.where(ge, starts[b], start)
        width = jnp.where(ge, widths[b], width)
        blk = jnp.where(ge, b, blk)
    return blk, start, width


def _decode_side(lines, idx, f, starts, widths, r: int, F: int):
    blk, start, width = _block_lookup(lines, starts, widths)
    sel = _chain_select(f, idx, r)
    s = (lines - start - sel) % width
    return (blk * jnp.int32(2048) + s) * jnp.int32(F) + f


def _decode_body(key_ref, vs_ref, vd_ref, *, starts, widths, r: int, F: int):
    k = key_ref[0]  # [2, rows, d]: one row band of one shard
    fb = k % jnp.int32(F)
    rest = k // jnp.int32(F)
    fa = rest % jnp.int32(F)
    idx = rest // jnp.int32(F)
    ia = idx // jnp.int32(IDX_RADIX)
    ib = idx % jnp.int32(IDX_RADIX)
    rows = jax.lax.broadcasted_iota(jnp.int32, k.shape, 1) \
        + pl.program_id(1) * k.shape[1]
    cols = jax.lax.broadcasted_iota(jnp.int32, k.shape, 2)
    occ = k != EMPTY
    vs = _decode_side(rows, ia, fa, starts, widths, r, F)
    vd = _decode_side(cols, ib, fb, starts, widths, r, F)
    vs_ref[0] = jnp.where(occ, vs, EMPTY)
    vd_ref[0] = jnp.where(occ, vd, EMPTY)


def _band_rows(d: int) -> int:
    """Rows per grid step: a sublane-aligned band that divides d (the
    whole plane when d has no such divisor — only toy widths)."""
    for rows in (64, 32, 16, 8):
        if d % rows == 0:
            return rows
    return d


@functools.partial(jax.jit, static_argnames=("starts", "widths", "r", "F",
                                             "interpret"))
def cell_decode_kernel_sharded(key_plane, *, starts, widths, r: int, F: int,
                               interpret: bool = False):
    """Decode every cell's (source, destination) packed vids per shard.

    key_plane: [n_shards, 2, d, d] twin-leading packed keys (QueryPlanes
    layout). ``starts``/``widths``: the static block partition as tuples.
    Returns (vid_src, vid_dst), each [n_shards, 2, d, d] with EMPTY (-1)
    on unoccupied cells. Grid ``(n_shards, d // rows)``; ``interpret`` is
    for the CPU parity tests only.
    """
    S, _, d, _ = key_plane.shape
    rows = _band_rows(d)
    band = pl.BlockSpec((1, 2, rows, d), lambda h, i: (h, 0, i, 0))
    vs, vd = pl.pallas_call(
        functools.partial(_decode_body, starts=starts, widths=widths,
                          r=r, F=F),
        grid=(S, d // rows),
        in_specs=[band],
        out_specs=[band, band],
        out_shape=[
            jax.ShapeDtypeStruct(key_plane.shape, jnp.int32),
            jax.ShapeDtypeStruct(key_plane.shape, jnp.int32),
        ],
        interpret=interpret,
        name="cell_decode",
    )(key_plane)
    return vs, vd


def cell_decode_xla(key_plane, *, starts, widths, r: int, F: int):
    """Compiled pure-XLA twin of ``cell_decode_kernel_sharded`` — the same
    closed-form inversion via the shared ``hashing.decode_line_vid`` seam;
    bit-identical (integer ops only). key_plane: [S, 2, d, d] twin-leading.
    Traced (not jitted) — compose inside a jitted caller.
    """
    from repro.core import hashing as hsh

    d = key_plane.shape[-1]
    ia, ib, fa, fb = hsh.unpack_key(key_plane, F)
    rows = jnp.arange(d, dtype=jnp.int32)[None, None, :, None]
    cols = jnp.arange(d, dtype=jnp.int32)[None, None, None, :]
    vs = hsh.decode_line_vid(rows, ia, fa, starts, widths, r, F)
    vd = hsh.decode_line_vid(cols, ib, fb, starts, widths, r, F)
    occ = key_plane != EMPTY
    return jnp.where(occ, vs, EMPTY), jnp.where(occ, vd, EMPTY)
