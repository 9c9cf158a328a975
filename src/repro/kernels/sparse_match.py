"""Pallas TPU kernel: sparse pattern matching (the paper's Key Comparator +
Distance Accumulator, fused — DESIGN.md §11).

The FPGA's sequential merge-join becomes a *match matrix* on the MXU: for
one document row (K ids, K values) of an ELL tile and a (merged
multi-query) id/value tile,

    eq[q, k]    = (q_ids[q] == doc_ids[k])            # Key Comparator
    matched     = q_vals^T @ eq                        # [L, K]
    scoresΔ     = sum_K (doc_vals ⊙ matched)           # Distance Accumulator

Query batching (the paper's L dimension, §II.A / Table 2) appears as the L
value rows of the merged query stream: one id stream, L value rows,
raising arithmetic intensity exactly like the paper's 20-kernel / 3-query
configuration.

Layout (what Mosaic accepts): document words lie along lanes, one
document row per loop step, and the query ids along sublanes (a
``[Qp, 1]`` column), so the compare broadcasts without any relayout.
The L value rows are padded to ``SUBLANES`` and split exactly into three
bfloat16 parts (``split3``), so the match matmul is one bf16 MXU pass
whose f32 sum reproduces every fp32 query value bit for bit.

Grid: (doc_tiles, query_tiles); the query tile (the paper's 8 KB "query
memory") is pinned in VMEM per BlockSpec, document tiles stream through
VMEM double-buffered by the Pallas pipeline (the prefetch-predictor
analogue — no rewind exists in this formulation, so there is nothing to
mispredict).

Sentinels: document padding is -1, query padding is -2 — they never match
each other or real ids.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

DOC_PAD = -1
QUERY_PAD = -2
SUBLANES = 16     # L pads to a multiple of the bf16 sublane tile


def split3(x: Array) -> Tuple[Array, Array, Array]:
    """Exact three-way bfloat16 split of fp32 values: ``hi + mid + lo ==
    x`` (summed in that order in fp32). A 0/1 matrix times each part on
    the MXU is exact, so three bf16 passes carry full fp32 precision
    whatever the compiler's default matmul precision is."""
    hi = x.astype(jnp.bfloat16)
    r = x - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def query_operands(q_ids: Array, q_vals: Array, block_query: int
                   ) -> Tuple[Array, Array, int, int]:
    """Merged query stream ([Qm] ids, [Qm, L] values) -> kernel operands:
    ``q_col`` [Qp, 1] int32 (pads remapped to QUERY_PAD), ``qv3`` [3·Lk,
    Qp] bf16 (transposed values, L padded to Lk, split by ``split3``),
    the query tile ``tq`` and ``Lk``. A zero-length stream still pads to
    one full tile, so the kernel never launches an empty grid."""
    Qm, L_ = q_vals.shape
    tq = min(block_query, max(Qm, 8))
    Qp = max(-(-Qm // tq) * tq, tq)
    lk = -(-max(L_, 1) // SUBLANES) * SUBLANES
    qi = jnp.pad(q_ids, (0, Qp - Qm), constant_values=QUERY_PAD)
    qi = jnp.where(qi < 0, QUERY_PAD, qi).astype(jnp.int32)
    qv = jnp.pad(q_vals.astype(jnp.float32).T, ((0, lk - L_), (0, Qp - Qm)))
    qv3 = jnp.concatenate(split3(qv), axis=0)
    return qi[:, None], qv3, tq, lk


def match_row(q_col: Array, qv3: Array, ids_row: Array, lk: int) -> Array:
    """One document row against one query tile: ``q_col`` [tq, 1],
    ``qv3`` [3·lk, tq] bf16, ``ids_row`` [1, W] int32 -> the query value
    each of the W words matched, per query row: [lk, W] fp32."""
    eq = (q_col == ids_row).astype(jnp.bfloat16)                 # [tq, W]
    m = jnp.dot(qv3, eq, preferred_element_type=jnp.float32)     # [3lk, W]
    return (m[:lk] + m[lk:2 * lk]) + m[2 * lk:]


def _kernel(doc_ids_ref, doc_vals_ref, q_ref, qv_ref, out_ref):
    j = pl.program_id(1)
    td = doc_ids_ref.shape[0]
    lk = out_ref.shape[0]
    q_col, qv3 = q_ref[...], qv_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (lk, td), 1)

    def row(d, acc):
        ids = doc_ids_ref[pl.ds(d, 1), :]
        vals = doc_vals_ref[pl.ds(d, 1), :].astype(jnp.float32)
        pp = vals * match_row(q_col, qv3, ids, lk)               # [lk, K]
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
def sparse_match(doc_ids: Array, doc_vals: Array, q_col: Array, qv3: Array,
                 *, block_docs: int = 128, block_query: int = 512,
                 interpret: bool = False) -> Array:
    """doc_ids/doc_vals: [D, K]; ``q_col``/``qv3`` from
    ``query_operands``. D % block_docs == 0 and Qp % block_query == 0
    (ops.py pads). Returns transposed correlation scores [Lk, D] fp32."""
    D, K = doc_ids.shape
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
            pl.BlockSpec((td, K), lambda i, j: (i, 0)),
            pl.BlockSpec((tq, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((3 * lk, tq), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((lk, td), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((lk, D), jnp.float32),
        interpret=interpret,
    )(doc_ids, doc_vals, q_col, qv3)
