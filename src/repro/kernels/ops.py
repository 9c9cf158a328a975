"""jit'd public wrappers around the sparse_match kernel family.

Handles padding to tile multiples, merged multi-query streams, sentinel
conventions and cosine normalization. ``backend``:
  - "pallas": the TPU kernel (interpret mode on CPU — used by tests;
    see ``interpret_mode``)
  - "jnp":    gather-based scoring (engine default on CPU; also the
              in-memory CPU baseline of the paper's Fig. 13)
  - "pallas_packed": the Fig. 8 packed-word kernel (uint32 corpus)
  - "pallas_fused": decode+match+top-k in one kernel over packed doc
    tiles — wrapped by ``fused_topk`` (DESIGN.md §12), which returns
    folded [L, k] winners instead of a correlation matrix
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.topk import fold_topk
from repro.kernels import ref as ref_mod
from repro.kernels.fused import fused_match_topk
from repro.kernels.sparse_match import query_operands, sparse_match
from repro.kernels.sparse_match_packed import sparse_match_packed

Array = jax.Array


def _pad_to(x: Array, n: int, axis: int, fill) -> Array:
    need = n - x.shape[axis]
    if need <= 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, need)
    return jnp.pad(x, pads, constant_values=fill)


def merge_queries(q_ids: np.ndarray, q_vals: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Stack L queries ([L, Qn] ids, [L, Qn] vals, pad<0) into one merged
    id stream with L value columns: ids [Qm], vals [Qm, L].

    Rows with zero non-pad terms simply contribute no items (their value
    column stays all-zero, so they score 0 against everything), and an
    empty batch (L = 0, or every row empty) yields the well-defined
    zero-length stream — not a concatenate error."""
    L_, _ = q_ids.shape
    if L_ == 0:
        return np.empty(0, np.int32), np.zeros((0, 0), np.float32)
    ids_out, vals_out = [], []
    for l in range(L_):
        keep = q_ids[l] >= 0
        ids_out.append(q_ids[l][keep])
        v = np.zeros((keep.sum(), L_), np.float32)
        v[:, l] = q_vals[l][keep]
        vals_out.append(v)
    ids = np.concatenate(ids_out).astype(np.int32)
    vals = np.concatenate(vals_out, axis=0)
    order = np.argsort(ids, kind="stable")
    return ids[order], vals[order]


def interpret_mode() -> bool:
    """Whether the Pallas backends run in interpret mode: on ``cpu``
    (where the test suites run) yes, compiled by Mosaic on ``tpu``. Any
    other platform raises — a Pallas backend never falls back to the
    interpreter in silence."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas backends run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the default backend is {platform!r} — use backend='jnp' there")


@functools.partial(jax.jit, static_argnames=("backend", "block_docs",
                                             "block_query", "vocab_size"))
def correlate(doc_ids: Array, doc_vals: Array, q_ids: Array, q_vals: Array,
              *, backend: str = "jnp", vocab_size: int = 0,
              block_docs: int = 128, block_query: int = 512) -> Array:
    """Correlation (cosine numerator) [D, L]."""
    D = doc_ids.shape[0]
    L_ = q_vals.shape[1]
    if D == 0 or L_ == 0:
        # degenerate program shapes (empty corpus / empty batch): the
        # well-defined zero correlation, not an empty-grid kernel launch
        return jnp.zeros((D, L_), jnp.float32)
    if backend in ("pallas", "pallas_packed"):
        td = min(block_docs, max(D, 8))
        Dp = -(-D // td) * td
        q_col, qv3, tq, _ = query_operands(q_ids, q_vals, block_query)
        interpret = interpret_mode()
        if backend == "pallas_packed":
            # doc_ids here is the packed uint32 corpus (Fig. 8 in HBM),
            # reinterpreted as int32 — the pad word is then -1
            dp = _pad_to(jax.lax.bitcast_convert_type(doc_ids, jnp.int32),
                         Dp, 0, -1)
            out = sparse_match_packed(dp, q_col, qv3, block_docs=td,
                                      block_query=tq, interpret=interpret)
        else:
            di = _pad_to(doc_ids, Dp, 0, -1)
            dv = _pad_to(doc_vals, Dp, 0, 0.0)
            out = sparse_match(di, dv, q_col, qv3, block_docs=td,
                               block_query=tq, interpret=interpret)
        return out[:L_, :D].T
    assert vocab_size > 0, "jnp backend needs vocab_size"
    qi = jnp.where(q_ids < 0, -1, q_ids)
    return ref_mod.sparse_match_ref(doc_ids, doc_vals, qi, q_vals, vocab_size)


def cosine_scores(corr: Array, doc_norms: Array, q_norms: Array) -> Array:
    """corr: [D, L]; doc_norms: [D]; q_norms: [L] -> cosine in [-1, 1]."""
    denom = doc_norms[:, None] * q_norms[None, :]
    return jnp.where(denom > 0, corr / jnp.maximum(denom, 1e-12), -jnp.inf)


@functools.partial(jax.jit, static_argnames=("k", "block_docs",
                                             "block_query"))
def fused_topk(tiles: Array, q_ids: Array, q_vals: Array, q_norms: Array,
               *, k: int, block_docs: int, block_query: int = 512
               ) -> Tuple[Array, Array]:
    """The ``pallas_fused`` scoring surface: packed doc tiles ([T, R,
    128] int32 from ``kernels.fused.device_tiles``) + merged query
    stream -> folded (vals [L, k], ids [L, k]) winners. One kernel
    replaces the decode -> correlate -> local_topk dispatch chain
    (DESIGN.md §12).

    Each doc tile emits its best ``min(k, block_docs)`` candidates —
    never explicit pad entries mid-stream — and the fold concatenates
    them in tile order, so ties resolve exactly as a flat global top_k
    over document rows would (see ``core.topk.fold_topk``)."""
    T = tiles.shape[0]
    L_ = q_vals.shape[1]
    kp = min(k, block_docs)
    if T == 0 or L_ == 0:
        # empty corpus / empty batch: the same (-inf, -1) no-result rows
        # the staged path's local_topk padding produces
        return (jnp.full((L_, k), -jnp.inf, jnp.float32),
                jnp.full((L_, k), -1, jnp.int32))
    q_col, qv3, tq, lk = query_operands(q_ids, q_vals, block_query)
    qn = _pad_to(q_norms.astype(jnp.float32), lk, 0, 0.0)[:, None]
    pv, pi = fused_match_topk(tiles, q_col, qv3, qn,
                              block_docs=block_docs, kp=kp,
                              block_query=tq, interpret=interpret_mode())
    # concatenate per-tile candidates in tile order, then fold to k
    cv = jnp.transpose(pv[:, :L_], (1, 0, 2)).reshape(L_, T * kp)
    ci = jnp.transpose(pi[:, :L_], (1, 0, 2)).reshape(L_, T * kp)
    return fold_topk(cv, ci, k)
