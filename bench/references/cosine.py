"""The plain reference for bag-of-words search: exact cosine top-k.

``reference_cos`` and ``check_rows`` are copied from the repository's chip
smoke test, where they proved sound; the reference computes in float64
with numpy over the generated corpus, and takes nothing the program made.
``Reference`` is the same float64 cosine as one sparse matrix product
(scipy), fast enough to check a run's sample at full size; the CPU tests
hold the two to the same answers. ``judge`` turns the comparison into
numbers, each held to a limit.
``control_topk`` is the same cosine computed one precision lower
(bfloat16, on the default JAX device): put in the program's place, it has
to be judged not correct.
"""
from __future__ import annotations

import numpy as np

# the limit, from the readings in PERF.md ("How correct is decided"): sound
# runs of the program read a widest score gap of 1.79e-7 at most (float32
# rounding), the bfloat16 control 2.36e-3 at least
SCORE_GAP_LIMIT = 5e-5


def reference_cos(ids: np.ndarray, vals: np.ndarray, q_ids: np.ndarray,
                  q_vals: np.ndarray, vocab: int,
                  chunk: int = 1 << 17) -> np.ndarray:
    """Cosine of every query row [L, Qn] (pad < 0) against every corpus
    row -> [L, n_docs] float64 (-inf where a norm is zero)."""
    n_docs = ids.shape[0]
    norms = np.sqrt((vals.astype(np.float64) ** 2).sum(1))
    out = np.empty((q_ids.shape[0], n_docs))
    for l in range(q_ids.shape[0]):
        keep = q_ids[l] >= 0
        dense = np.zeros(vocab + 1)
        np.add.at(dense, q_ids[l][keep], q_vals[l][keep].astype(np.float64))
        qn = np.sqrt((q_vals[l][keep].astype(np.float64) ** 2).sum())
        for lo in range(0, n_docs, chunk):
            rows = slice(lo, lo + chunk)
            g = dense[np.where(ids[rows] >= 0, ids[rows], vocab)]
            corr = (g * vals[rows]).sum(1)
            denom = norms[rows] * qn
            with np.errstate(divide="ignore", invalid="ignore"):
                out[l, rows] = np.where(denom > 0, corr / denom, -np.inf)
    return out


class Reference:
    """Float64 cosine of query rows against the whole corpus: the corpus
    as a CSR matrix [n_docs, vocab + 1] of its counts, built once."""

    def __init__(self, ids: np.ndarray, vals: np.ndarray, vocab: int):
        import scipy.sparse as sp
        rows, cols = np.nonzero(ids >= 0)
        self.vocab = vocab
        self.docs = sp.csr_matrix(
            (vals[rows, cols].astype(np.float64), (rows, ids[rows, cols])),
            shape=(ids.shape[0], vocab + 1))
        self.norms = np.sqrt(self.docs.multiply(self.docs).sum(1)).A1

    def cos(self, q_ids: np.ndarray, q_vals: np.ndarray) -> np.ndarray:
        """[L, Qn] query rows (pad < 0) -> [L, n_docs] float64 cosines
        (-inf where a norm is zero)."""
        import scipy.sparse as sp
        L = q_ids.shape[0]
        rows, cols = np.nonzero(q_ids >= 0)
        q = sp.csc_matrix(
            (q_vals[rows, cols].astype(np.float64), (q_ids[rows, cols], rows)),
            shape=(self.vocab + 1, L))
        qn = np.sqrt(q.multiply(q).sum(0)).A1
        corr = (self.docs @ q).toarray().T
        denom = qn[:, None] * self.norms[None, :]
        with np.errstate(divide="ignore", invalid="ignore"):
            return np.where(denom > 0, corr / denom, -np.inf)


def _top(row: np.ndarray, k: int) -> np.ndarray:
    """The k largest values of a row, descending."""
    if row.size <= k:
        return -np.sort(-row)
    return -np.sort(-np.partition(row, row.size - k)[row.size - k:])


def check_rows(doc_ids, scores, ref: np.ndarray, k: int, self_docs=None,
               tol: float = SCORE_GAP_LIMIT):
    """Top-k rows [L, k] vs the reference cosines [L, N]: positional
    scores match the reference's k best, every returned id really has
    its score (so ids differ only inside a tie), ids are unique, and a
    self-query's own doc leads at cosine 1.0. Returns a list of
    problems (empty: agreement)."""
    doc_ids = np.atleast_2d(np.asarray(doc_ids))
    scores = np.atleast_2d(np.asarray(scores, np.float64))
    bad = []
    for l in range(ref.shape[0]):
        want = -np.sort(-ref[l])[:k]
        ids, sc = doc_ids[l], scores[l]
        if not np.allclose(sc, want, rtol=0, atol=tol):
            bad.append(f"row {l}: scores {sc[:4]}.. != ref {want[:4]}..")
            continue
        real = ids[ids >= 0]
        if real.size != np.unique(real).size:
            bad.append(f"row {l}: duplicate ids {ids}")
        if not np.allclose(ref[l][real], sc[ids >= 0], rtol=0, atol=tol):
            bad.append(f"row {l}: an id does not have its score")
        if self_docs is not None:
            top = ids[np.abs(sc - sc[0]) <= tol]
            if self_docs[l] not in top or abs(sc[0] - 1.0) > tol:
                bad.append(f"row {l}: self-query {self_docs[l]} got "
                           f"{ids[0]} at {sc[0]!r}")
    return bad


def _gap(a: np.ndarray, b: np.ndarray) -> float:
    """Widest |a - b|, where equal infinities agree and any other
    non-finite difference is an infinite gap."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    same = (a == b)
    with np.errstate(invalid="ignore"):
        d = np.where(same, 0.0, np.abs(a - b))
    d = np.where(np.isnan(d), np.inf, d)
    return float(d.max()) if d.size else 0.0


def judge(doc_ids, scores, ref: np.ndarray, k: int, self_docs) -> dict:
    """The numbers ``correct`` is decided on, for one block of answered
    rows against their reference cosines:

    score_gap   widest gap between a returned score and the reference's
                score at that rank
    id_gap      widest gap between a returned score and the reference's
                cosine of the id returned with it (ids may differ from
                the reference only inside a tie)
    dup_rows    rows that return one id twice
    self_miss   rows whose own document is not among the ids tied at the
                top, at cosine 1.0
    """
    doc_ids = np.atleast_2d(np.asarray(doc_ids, np.int64))
    scores = np.atleast_2d(np.asarray(scores, np.float64))
    out = {"score_gap": 0.0, "id_gap": 0.0, "dup_rows": 0, "self_miss": 0}
    for l in range(ref.shape[0]):
        want = _top(ref[l], k)
        ids, sc = doc_ids[l], scores[l]
        out["score_gap"] = max(out["score_gap"], _gap(sc, want))
        real = ids >= 0
        # a missing id where the reference has a document is a wrong answer
        got = np.where(real, ref[l][np.where(real, ids, 0)], -np.inf)
        out["id_gap"] = max(out["id_gap"], _gap(got, sc))
        if ids[real].size != np.unique(ids[real]).size:
            out["dup_rows"] += 1
        with np.errstate(invalid="ignore"):
            top = ids[np.abs(sc - sc[0]) <= SCORE_GAP_LIMIT]
        if self_docs[l] not in top or not abs(sc[0] - 1.0) <= SCORE_GAP_LIMIT:
            out["self_miss"] += 1
    return out


def merge_judgements(parts) -> dict:
    out = {"score_gap": 0.0, "id_gap": 0.0, "dup_rows": 0, "self_miss": 0}
    for p in parts:
        for key in ("score_gap", "id_gap"):
            out[key] = max(out[key], p[key])
        for key in ("dup_rows", "self_miss"):
            out[key] += p[key]
    return out


LIMITS = {"score_gap": SCORE_GAP_LIMIT, "id_gap": SCORE_GAP_LIMIT,
          "dup_rows": 0, "self_miss": 0}


def control_topk(ids: np.ndarray, vals: np.ndarray, q_ids: np.ndarray,
                 q_vals: np.ndarray, vocab: int, k: int):
    """The reference's cosine top-k computed in bfloat16 on the default
    JAX device -> (doc_ids [L, k], scores [L, k] float32). The control:
    the step below the float32 the configuration states."""
    import jax
    import jax.numpy as jnp

    bf = jnp.bfloat16

    @jax.jit
    def one(d_ids, d_vals, dense, qn):
        g = dense[jnp.where(d_ids >= 0, d_ids, vocab)]
        v = d_vals.astype(bf)
        corr = (g * v).sum(1, dtype=bf)
        dn = jnp.sqrt((v * v).sum(1, dtype=bf))
        cos = jnp.where(dn > 0, corr / (dn * qn), -jnp.inf).astype(bf)
        return jax.lax.top_k(cos.astype(jnp.float32), k)

    d_ids, d_vals = jax.device_put(ids), jax.device_put(vals)
    out_i = np.empty((q_ids.shape[0], k), np.int64)
    out_s = np.empty((q_ids.shape[0], k), np.float32)
    for l in range(q_ids.shape[0]):
        keep = q_ids[l] >= 0
        dense = np.zeros(vocab + 1, np.float32)
        np.add.at(dense, q_ids[l][keep], q_vals[l][keep])
        qn = np.sqrt((q_vals[l][keep].astype(np.float32) ** 2).sum())
        s, i = one(d_ids, d_vals, jnp.asarray(dense, bf), jnp.asarray(qn, bf))
        out_s[l], out_i[l] = np.asarray(s), np.asarray(i)
    return out_i, out_s
