"""Fused decode + match + partial-top-k Pallas kernel (DESIGN.md §12).

The paper's accelerator wins by *fusion*: the flash interface logic
decodes the Fig. 8 stream, the key comparator + distance accumulator
match it, and only high-score document ids leave the chip — one pass,
no intermediate materialization. The staged software path still runs

    decode_to_ell (host numpy) -> correlate (kernel) -> local_topk

as three dispatches with a host-resident ELL intermediate ([D, K] int32
ids + [D, K] float32 vals + norms) between the first two. This module
collapses the chain into one ``pallas_call`` over the packed stream
itself:

  - **decode** — in-kernel VPU shifts/masks split each 32-bit word into
    header (bit 31 set: ``[1 | docID:31]``) or pair (``[0 | wordID:19 |
    count:12]``). A prefix count of the header bits (two exact 0/1
    matmuls on the MXU) assigns every word to its document row; a
    one-hot row matrix then turns segment reductions (doc id, per-doc
    norm, per-doc score) into MXU matmuls;
  - **match** — ``sparse_match.match_row`` one 128-word row at a time:
    ``eq = (q_ids == ids)``, ``q_vals^T @ eq``, scaled by the decoded
    counts and folded per document row;
  - **top-k** — the epilogue (last query-tile grid step) computes the
    cosine scores against in-kernel doc norms and emits each doc tile's
    ``min(k, block_docs)`` best candidates by k rounds of
    max-and-mask; the host-side wrapper folds the per-tile candidate
    lists with the ``core.topk`` primitives.

Host staging is reduced to ``tile_stream``: an O(n) boundary-index pass
that splits the raw stream at document boundaries into fixed-capacity
tiles (``cap = block_docs * (1 + nnz_pad)`` words, pad word 0xFFFFFFFF)
so no document straddles a grid block. On upload (``device_tiles``) each
tile is laid out as ``[R, 128]`` words — the capacity rounded up to whole
(8, 128) vreg tiles — and reinterpreted as int32, the layout and dtype
Mosaic's block and cast rules accept. No ELL arrays, no float
conversion, no norms are materialized on the host — 4 B/word travels to
the device exactly as it sits in the segment file.

Numerics: counts are 12-bit integers, so in the no-overflow regime
(score and norm partial sums below 2**24) every accumulation order is
exact in fp32, every MXU operand is split exactly into bf16 parts
(``sparse_match.split3``), and the fused result is *bit-identical* to
the staged ``jnp`` reference in interpret mode — including the ``sqrt``
of the norms. tests/test_fused_kernel.py proves this on every serving
surface. On the chip, division and ``sqrt`` follow the TPU's rounding.

Tiling (``block_docs``, ``block_query``) comes from the strategy
classes in ``kernels.tiling``; shapes are memoized per L-bucket so the
§7 compile-cache bound (<= log2(max_batch)+1 programs per shape
family) still holds. ``interpret=True`` runs the same kernel on CPU —
the differential suites in CI exercise the identical code path.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.stream_format import (HEADER_BIT, KEY_BITS, KEY_MASK,
                                      MAX_DOC_ID, VAL_BITS, VAL_MASK)
from repro.kernels.sparse_match import DOC_PAD, match_row, split3

Array = jax.Array

PAD_WORD = np.uint32(0xFFFFFFFF)
LANES = 128
_HIGHEST = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))            # contract the lane dims: a @ b.T


class PackedSlab(NamedTuple):
    """A corpus slab in fused-kernel layout: the Fig. 8 stream split
    into fixed-capacity doc tiles, still packed. The fused scoring unit
    — the counterpart of the staged path's DeviceSlab."""
    tiles: jax.Array      # [T, R, 128] int32 view of the uint32 words


# ---------------------------------------------------------------------------
# host-side stream tiling (boundary index pass — NOT an ELL decode)
# ---------------------------------------------------------------------------
def kernel_width(block_docs: int, nnz_pad: int) -> int:
    """Words per tile in the kernel layout: the tile capacity rounded up
    to whole (8, 128) 32-bit vreg tiles."""
    cap = block_docs * (1 + nnz_pad)
    return -(-cap // (8 * LANES)) * 8 * LANES


def tile_stream(stream: np.ndarray, *, block_docs: int, nnz_pad: int,
                pad_docs_to: Optional[int] = None,
                width: Optional[int] = None
                ) -> Tuple[np.ndarray, int, int]:
    """Split a Fig. 8 uint32 stream into ``[T, cap]`` fixed-capacity doc
    tiles for the fused kernel. Applies the exact truncation rule of
    ``decode_to_ell`` (pairs beyond ``nnz_pad`` per document are
    dropped) so fused stats and scores match the staged path.

    ``pad_docs_to`` pads the tile count to ``ceil(pad_docs_to /
    block_docs)`` (all-PAD rows) so every segment of a store shares one
    program shape — the fused analogue of ``Corpus.pad_docs_to``.
    ``width`` (>= cap) widens every tile with PAD words, e.g. to
    ``kernel_width``.

    Returns ``(tiles, n_docs, n_truncated)``.
    """
    stream = np.asarray(stream, np.uint32)
    cap = block_docs * (1 + nnz_pad)
    width = cap if width is None else int(width)
    if width < cap:
        raise ValueError(f"width {width} < tile capacity {cap}")
    is_hdr = (stream & HEADER_BIT) != 0
    n_docs = int(is_hdr.sum())
    target = n_docs if pad_docs_to is None else int(pad_docs_to)
    if target < n_docs:
        raise ValueError(f"pad_docs_to {target} < n_docs {n_docs}")
    n_tiles = -(-target // block_docs) if target else 0
    if n_docs == 0:
        return np.full((n_tiles, width), PAD_WORD, np.uint32), 0, 0
    if bool((stream == PAD_WORD).any()):
        # header word of doc_id MAX_DOC_ID collides with the pad
        # sentinel; the staged backends handle it, the fused one refuses
        raise ValueError(
            f"stream contains word 0x{int(PAD_WORD):08X} (doc_id "
            f"{MAX_DOC_ID}), which aliases the fused-kernel pad")
    # per-word document segment + within-document position
    hdr_pos = np.flatnonzero(is_hdr)
    seg = np.cumsum(is_hdr) - 1
    pos = np.arange(stream.size) - hdr_pos[seg]    # 0 = header, 1.. = pair
    keep = is_hdr | (pos <= nnz_pad)
    n_trunc = int(stream.size - int(keep.sum()))
    kept = stream[keep]
    # re-index the kept stream and scatter into (tile, column) slots
    is_hdr_k = (kept & HEADER_BIT) != 0
    hdr_pos_k = np.flatnonzero(is_hdr_k)
    doc_of = np.cumsum(is_hdr_k) - 1               # document per word
    tile_of = doc_of // block_docs
    tile_base = hdr_pos_k[tile_of * block_docs]    # tile's first word
    col = np.arange(kept.size) - tile_base
    tiles = np.full((n_tiles, width), PAD_WORD, np.uint32)
    tiles[tile_of, col] = kept
    return tiles, n_docs, n_trunc


def device_tiles(stream: np.ndarray, *, block_docs: int, nnz_pad: int,
                 pad_docs_to: Optional[int] = None,
                 device=None) -> Tuple[PackedSlab, int, int]:
    """``tile_stream`` at ``kernel_width``, reshaped to ``[T, R, 128]``,
    viewed as int32 and uploaded to ``device`` (None: JAX's default).
    Returns ``(slab, n_docs, n_truncated)``."""
    width = kernel_width(block_docs, nnz_pad)
    tiles, n_docs, n_trunc = tile_stream(
        stream, block_docs=block_docs, nnz_pad=nnz_pad,
        pad_docs_to=pad_docs_to, width=width)
    tiles = tiles.view(np.int32).reshape(-1, width // LANES, LANES)
    return PackedSlab(jax.device_put(tiles, device)), n_docs, n_trunc


def corpus_to_stream(corpus) -> np.ndarray:
    """Re-encode an ELL ``Corpus`` (integral Fig. 8-representable
    counts) as the packed uint32 stream — the bridge for surfaces that
    only hold decoded rows (resident engine corpus, ingest memtable).
    Padding rows (doc_id < 0) are skipped; within-row pair order is
    preserved. Raises for values the 19/12-bit packing cannot carry."""
    ids = np.asarray(corpus.ids)
    vals = np.asarray(corpus.vals)
    doc_ids = np.asarray(corpus.doc_ids)
    rows = doc_ids >= 0
    valid = (ids >= 0) & rows[:, None]
    v = vals[valid]
    if v.size and (not np.all(v == np.round(v)) or v.min() < 0
                   or v.max() > VAL_MASK):
        raise ValueError(
            "fused/packed backends need integral counts in "
            f"[0, {VAL_MASK}] (Fig. 8 12-bit packing); use the jnp or "
            "pallas backend for arbitrary float values")
    if ids[valid].size and int(ids[valid].max()) > KEY_MASK:
        raise ValueError(f"word id exceeds {KEY_BITS}-bit packing")
    if rows.any() and int(doc_ids[rows].max()) >= MAX_DOC_ID:
        raise ValueError(f"doc_id >= {MAX_DOC_ID} aliases the fused pad")
    lens = valid.sum(1)[rows]
    d_ids = doc_ids[rows].astype(np.uint32)
    starts = np.zeros(d_ids.size, np.int64)
    np.cumsum(lens[:-1] + 1, out=starts[1:])
    out = np.empty(int(lens.sum() + d_ids.size), np.uint32)
    out[starts] = HEADER_BIT | d_ids
    r, c = np.nonzero(valid[rows])
    rank = np.arange(r.size) - np.searchsorted(r, r)
    out[starts[r] + 1 + rank] = (
        (ids[rows][r, c].astype(np.uint32) << VAL_BITS)
        | vals[rows][r, c].astype(np.uint32))
    return out


# ---------------------------------------------------------------------------
# the fused kernel
# ---------------------------------------------------------------------------
def _fused_kernel(tiles_ref, q_ref, qv_ref, qn_ref,
                  vals_out_ref, ids_out_ref,
                  ids_scr, vals_scr, row_scr, stats_scr, corr_scr, *,
                  nq: int):
    """Grid (doc_tiles, query_tiles), query axis innermost. The decoded
    tile (word ids, counts, doc row per word), the per-doc stats and the
    correlation accumulator persist in scratch across the query axis;
    the prologue decodes once per doc tile, the epilogue ranks once."""
    j = pl.program_id(1)
    n_rows = tiles_ref.shape[1]
    lk, bd = corr_scr.shape
    kp = vals_out_ref.shape[2]
    f32 = jnp.float32
    slot = jax.lax.broadcasted_iota(jnp.int32, (bd, LANES), 0)

    def onehot(row):                      # [1, 128] doc rows -> [bd, 128]
        return (slot == row).astype(jnp.bfloat16)

    @pl.when(j == 0)
    def _prologue():
        # -- in-kernel Fig. 8 decode (VPU shifts/masks) ----------------
        w = tiles_ref[0]                                 # [R, 128] int32
        is_pad = w == -1                                 # PAD_WORD
        is_hdr = jnp.logical_and(w < 0, jnp.logical_not(is_pad))
        pair = jnp.logical_not(jnp.logical_or(is_pad, is_hdr))
        ids_scr[...] = jnp.where(pair, (w >> VAL_BITS) & KEY_MASK, DOC_PAD)
        vals_scr[...] = jnp.where(pair, (w & VAL_MASK).astype(f32), 0.0)
        # doc row per word = headers at or before it in stream order - 1:
        # an inclusive prefix within each 128-word row plus the headers
        # of all earlier rows, both as exact 0/1 matmuls
        h = is_hdr.astype(f32)
        a = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
        b = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
        within = jnp.dot(h, (a <= b).astype(f32), precision=_HIGHEST,
                         preferred_element_type=f32)
        totals = jnp.dot(h, jnp.ones((LANES, LANES), f32),
                         precision=_HIGHEST, preferred_element_type=f32)
        r = jax.lax.broadcasted_iota(jnp.int32, (n_rows, n_rows), 0)
        s = jax.lax.broadcasted_iota(jnp.int32, (n_rows, n_rows), 1)
        before = jnp.dot((s < r).astype(f32), totals, precision=_HIGHEST,
                         preferred_element_type=f32)
        row_scr[...] = (within + before).astype(jnp.int32) - 1

        # -- per-doc stats, one-hot folded on the MXU ------------------
        # rows 0-3: the header's doc id in bytes (each exact in bf16),
        # row 4: header present, rows 5-7: split3 of the squared count
        k16 = jax.lax.broadcasted_iota(jnp.int32, (16, LANES), 0)

        def stats_row(i, acc):
            wi = tiles_ref[0, pl.ds(i, 1), :]
            hdr = jnp.logical_and(wi < 0, wi != -1)
            doc = jnp.where(hdr, wi & MAX_DOC_ID, 0)
            v = vals_scr[pl.ds(i, 1), :]
            parts = [((doc >> (8 * c)) & 0xFF).astype(f32) for c in range(4)]
            parts.append(hdr.astype(f32))
            parts += [p.astype(f32) for p in split3(v * v)]
            rows = jnp.zeros((16, LANES), f32)
            for c, p in enumerate(parts):
                rows = jnp.where(k16 == c, p, rows)
            return acc + jax.lax.dot_general(
                rows.astype(jnp.bfloat16), onehot(row_scr[pl.ds(i, 1), :]),
                _NT, preferred_element_type=f32)            # [16, bd]

        stats_scr[...] = jax.lax.fori_loop(0, n_rows, stats_row,
                                           jnp.zeros((16, bd), f32))
        corr_scr[...] = jnp.zeros_like(corr_scr)

    # -- match: one 128-word row at a time (MXU) -----------------------
    q_col, qv3 = q_ref[...], qv_ref[...]

    def match(i, acc):
        pp = vals_scr[pl.ds(i, 1), :] * match_row(
            q_col, qv3, ids_scr[pl.ds(i, 1), :], lk)          # [lk, 128]
        pp3 = jnp.concatenate([p.astype(f32) for p in split3(pp)], axis=0)
        c = jax.lax.dot_general(pp3.astype(jnp.bfloat16),
                                onehot(row_scr[pl.ds(i, 1), :]), _NT,
                                preferred_element_type=f32)   # [3lk, bd]
        return acc + ((c[:lk] + c[lk:2 * lk]) + c[2 * lk:])

    corr_scr[...] += jax.lax.fori_loop(0, n_rows, match,
                                       jnp.zeros((lk, bd), f32))

    # -- epilogue: cosine + per-tile partial top-k ---------------------
    @pl.when(j == nq - 1)
    def _epilogue():
        st = stats_scr[...]
        b = st[0:4].astype(jnp.int32)
        doc_id = b[0:1] | (b[1:2] << 8) | (b[2:3] << 16) | (b[3:4] << 24)
        doc_id = jnp.where(st[4:5] > 0, doc_id, -1)                 # [1, bd]
        dnorm = jnp.sqrt((st[5:6] + st[6:7]) + st[7:8])             # [1, bd]
        denom = dnorm * qn_ref[...]                                 # [lk, bd]
        cos = jnp.where(denom > 0,
                        corr_scr[...] / jnp.maximum(denom, 1e-12),
                        -jnp.inf)
        # invalid rows (tile padding) can never surface; real documents
        # keep their id whatever their score (see core.topk.local_topk)
        cos = jnp.where(doc_id >= 0, cos, -jnp.inf)
        # rank with NaN pinned above every finite score (lax.top_k's own
        # totalorder outside Pallas), ties to the lower row like top_k
        rank = jnp.where(jnp.isnan(cos), jnp.inf, cos)
        lane = jax.lax.broadcasted_iota(jnp.int32, (lk, bd), 1)
        col = jax.lax.broadcasted_iota(jnp.int32, (lk, kp), 1)
        ids_b = jnp.broadcast_to(doc_id, (lk, bd))

        def pick(t, carry):
            taken, out_v, out_i = carry
            avail = jnp.where(taken > 0, -jnp.inf, rank)
            best = jnp.max(avail, axis=1, keepdims=True)
            hit = jnp.logical_and(avail == best, taken == 0)
            idx = jnp.min(jnp.where(hit, lane, bd), axis=1, keepdims=True)
            sel = lane == idx
            v = jnp.max(jnp.where(sel, cos, -jnp.inf), axis=1, keepdims=True)
            i = jnp.max(jnp.where(sel, ids_b, -1), axis=1, keepdims=True)
            return (jnp.where(sel, 1, taken), jnp.where(col == t, v, out_v),
                    jnp.where(col == t, i, out_i))

        _, v, i = jax.lax.fori_loop(
            0, kp, pick, (jnp.zeros((lk, bd), jnp.int32),
                          jnp.full((lk, kp), -jnp.inf, f32),
                          jnp.full((lk, kp), -1, jnp.int32)))
        vals_out_ref[...] = v[None]
        ids_out_ref[...] = i[None]


@functools.partial(jax.jit, static_argnames=("block_docs", "kp",
                                             "block_query", "interpret"))
def fused_match_topk(tiles: Array, q_col: Array, qv3: Array,
                     q_norms: Array, *, block_docs: int, kp: int,
                     block_query: int = 512,
                     interpret: bool = False) -> Tuple[Array, Array]:
    """tiles: [T, R, 128] int32 (from ``device_tiles``); ``q_col`` [Qp,
    1] / ``qv3`` [3·Lk, Qp] from ``sparse_match.query_operands``;
    q_norms: [Lk, 1]. Qp % block_query == 0 (ops.py pads). Returns
    per-tile candidates (vals [T, Lk, kp], ids [T, Lk, kp]) — fold with
    ``core.topk.fold_topk``."""
    T, n_rows, _ = tiles.shape
    Qp = q_col.shape[0]
    lk = qv3.shape[0] // 3
    tq = min(block_query, Qp)
    assert Qp % tq == 0, (Qp, tq)
    nq = Qp // tq
    return pl.pallas_call(
        functools.partial(_fused_kernel, nq=nq),
        grid=(T, nq),
        in_specs=[
            pl.BlockSpec((1, n_rows, LANES), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((tq, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((3 * lk, tq), lambda i, j: (0, j)),
            pl.BlockSpec((lk, 1), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, lk, kp), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, lk, kp), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, lk, kp), jnp.float32),
            jax.ShapeDtypeStruct((T, lk, kp), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((n_rows, LANES), jnp.int32),
                        pltpu.VMEM((n_rows, LANES), jnp.float32),
                        pltpu.VMEM((n_rows, LANES), jnp.int32),
                        pltpu.VMEM((16, block_docs), jnp.float32),
                        pltpu.VMEM((lk, block_docs), jnp.float32)],
        interpret=interpret,
    )(tiles, q_col, qv3, q_norms)
