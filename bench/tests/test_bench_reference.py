"""The plain reference and the comparison that decides ``correct``: the
two float64 forms agree, a perturbed top-k fails, and the bfloat16
control is judged not correct."""
import numpy as np
import pytest

from bench import spec
from bench.generators import uci_bow
from bench.harness import _stack
from bench.references import cosine

CFG = dict(spec.resolve("pubmed-shard32.open").config, n_docs=6000)
K = CFG["top_k"]
VOCAB = CFG["vocab_size"]


@pytest.fixture(scope="module")
def case():
    corpus = uci_bow.generate(CFG, 99)
    docs = np.array([3, 250, 4999, 5998])
    q = _stack([uci_bow.more_like_this(corpus, int(d)) for d in docs])
    ref = cosine.reference_cos(corpus.ids, corpus.vals, *q, VOCAB)
    order = np.argsort(-ref, axis=1, kind="stable")[:, :K]
    return corpus, docs, q, ref, order, np.take_along_axis(ref, order, 1)


def test_sparse_reference_equals_the_copied_one(case):
    corpus, _, q, ref, *_ = case
    sparse = cosine.Reference(corpus.ids, corpus.vals, VOCAB).cos(*q)
    assert np.array_equal(sparse, ref)


def test_exact_topk_passes(case):
    _, docs, _, ref, ids, scores = case
    assert cosine.check_rows(ids, scores, ref, K, self_docs=docs) == []
    got = cosine.judge(ids, scores.astype(np.float32), ref, K, docs)
    assert all(got[k] <= cosine.LIMITS[k] for k in got), got


@pytest.mark.parametrize("fault", ["score", "id", "duplicate", "missing"])
def test_perturbed_topk_fails(case, fault):
    _, docs, _, ref, ids, scores = case
    ids, scores = ids.copy(), scores.copy()
    if fault == "score":
        scores[1, 3] += 1e-3
    elif fault == "id":
        ids[2, 4] = (ids[2, 4] + 1) % ref.shape[1]
        while np.isclose(ref[2, ids[2, 4]], scores[2, 4], atol=1e-4):
            ids[2, 4] = (ids[2, 4] + 1) % ref.shape[1]
    elif fault == "duplicate":
        ids[0, 5] = ids[0, 6]
    else:
        ids[3, 0], scores[3, 0] = -1, -np.inf
    assert cosine.check_rows(ids, scores, ref, K, self_docs=docs) != []
    got = cosine.judge(ids, scores, ref, K, docs)
    assert any(got[k] > cosine.LIMITS[k] for k in got), got


def test_bfloat16_control_is_judged_not_correct(case):
    corpus, docs, q, ref, *_ = case
    ids, scores = cosine.control_topk(corpus.ids, corpus.vals, *q, VOCAB, K)
    got = cosine.judge(ids, scores, ref, K, docs)
    assert got["score_gap"] > cosine.LIMITS["score_gap"], got
