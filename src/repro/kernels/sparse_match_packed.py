"""Packed-format sparse match kernel (beyond-paper optimization, §Perf C3).

The baseline kernel streams ELL (id int32, val float32) pairs = 8 B/nnz.
This variant keeps the corpus in HBM in (a tiled version of) the paper's
own Fig. 8 32-bit packing — [wordID:19 | count:12] with the top bit clear,
sentinel 0xFFFFFFFF for padding — and unpacks in-kernel with VPU
shifts/masks. 4 B/nnz halves HBM traffic per document; in the memory-bound
single-query regime that is a straight 2x docs/s.

The words reach the kernel reinterpreted as int32 (ops.py bitcasts), so
every unpack is a signed-integer op Mosaic lowers; the pad sentinel is
then -1. The merge-join -> match-matrix reformulation is shared with
``sparse_match`` (``match_row``); only the operand encoding differs.
ops.correlate(backend="pallas_packed") wraps it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

import numpy as np

from repro.kernels.sparse_match import DOC_PAD, match_row

Array = jax.Array

KEY_BITS = 19
VAL_BITS = 12
VAL_MASK = (1 << VAL_BITS) - 1
PAD_WORD = np.uint32(0xFFFFFFFF)


def pack(ids: Array, vals: Array) -> Array:
    """ELL (ids int32 -1-padded, vals float32 integral counts) -> uint32."""
    ids = np.asarray(ids)
    vals = np.asarray(vals)
    counts = np.clip(vals, 0, VAL_MASK).astype(np.uint32)
    packed = (ids.astype(np.int64) << VAL_BITS).astype(np.uint32) | counts
    return np.where(ids < 0, PAD_WORD, packed)


def _kernel(docs_ref, q_ref, qv_ref, out_ref):
    j = pl.program_id(1)
    td = docs_ref.shape[0]
    lk = out_ref.shape[0]
    q_col, qv3 = q_ref[...], qv_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (lk, td), 1)

    def row(d, acc):
        w = docs_ref[pl.ds(d, 1), :]                 # [1, K] int32 words
        valid = w != -1                              # PAD_WORD as int32
        ids = jnp.where(valid, w >> VAL_BITS, DOC_PAD)
        vals = (w & VAL_MASK).astype(jnp.float32)
        pp = jnp.where(valid, vals * match_row(q_col, qv3, ids, lk), 0.0)
        return jnp.where(lane == d, jnp.sum(pp, axis=1, keepdims=True), acc)

    scores = jax.lax.fori_loop(0, td, row, jnp.zeros((lk, td), jnp.float32))

    @pl.when(j == 0)
    def _init():
        out_ref[...] = scores

    @pl.when(j > 0)
    def _acc():
        out_ref[...] += scores


@functools.partial(jax.jit, static_argnames=("block_docs", "block_query",
                                             "interpret"))
def sparse_match_packed(docs_packed: Array, q_col: Array, qv3: Array, *,
                        block_docs: int = 128, block_query: int = 512,
                        interpret: bool = False) -> Array:
    """docs_packed: [D, K] int32 view of the Fig. 8 words (pad -1);
    ``q_col``/``qv3`` from ``sparse_match.query_operands``. Returns
    transposed correlation scores [Lk, D]."""
    D, K = docs_packed.shape
    Qp = q_col.shape[0]
    lk = qv3.shape[0] // 3
    td = min(block_docs, D)
    tq = min(block_query, Qp)
    assert D % td == 0 and Qp % tq == 0, (D, td, Qp, tq)
    return pl.pallas_call(
        _kernel,
        grid=(D // td, Qp // tq),
        in_specs=[
            pl.BlockSpec((td, K), lambda i, j: (i, 0)),
            pl.BlockSpec((tq, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((3 * lk, tq), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((lk, td), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((lk, D), jnp.float32),
        interpret=interpret,
    )(docs_packed, q_col, qv3)
