"""The corpus generator copy: seeded, at the source's shape, exact at
nnz_pad (no pair truncated)."""
import numpy as np
import pytest

from bench import spec
from bench.generators import uci_bow

CFG = dict(spec.resolve("pubmed-shard32.open").config, n_docs=20000)


@pytest.fixture(scope="module")
def corpus():
    return uci_bow.generate(CFG, 2**31 + 5)


def test_same_seed_same_corpus(corpus):
    again = uci_bow.generate(CFG, 2**31 + 5)
    assert np.array_equal(corpus.ids, again.ids)
    assert np.array_equal(corpus.vals, again.vals)
    other = uci_bow.generate(CFG, 2**31 + 6)
    assert not np.array_equal(corpus.ids, other.ids)


def test_large_seed_accepted():
    small = dict(CFG, n_docs=64)
    assert uci_bow.generate(small, 2**40 + 3).n_docs == 64


def test_shape_of_the_source(corpus):
    real = corpus.ids >= 0
    lens = real.sum(1)
    assert abs(lens.mean() - CFG["nnz_per_doc"]) < 0.5
    assert lens.min() >= 1 and lens.max() <= CFG["nnz_pad"]
    ids = corpus.ids[real]
    assert ids.min() >= 0 and ids.max() < CFG["vocab_size"]
    counts = corpus.vals[real]
    assert abs(counts.mean() - CFG["count_per_nnz"]) < 0.05
    assert counts.min() >= 1 and np.all(counts == np.round(counts))
    assert np.all(corpus.vals[~real] == 0)


def test_rows_sorted_distinct_and_left_packed(corpus):
    ids = corpus.ids
    real = ids >= 0
    # padding only after the real terms
    assert np.all(real[:, :-1] | ~real[:, 1:])
    both = real[:, 1:] & real[:, :-1]
    assert np.all(ids[:, 1:][both] > ids[:, :-1][both])


def test_no_pair_truncated_at_nnz_pad(corpus):
    """The store's own decode at nnz_pad reports zero truncated pairs."""
    from repro.core import stream_format
    stream = stream_format.encode(corpus.docs(0, 2000))
    *_, n_trunc = stream_format.decode_to_ell(stream, CFG["nnz_pad"])
    assert n_trunc == 0


def test_query_is_the_documents_bag(corpus):
    ids, vals = uci_bow.more_like_this(corpus, 17)
    keep = corpus.ids[17] >= 0
    assert np.array_equal(ids, corpus.ids[17][keep])
    assert np.array_equal(vals, corpus.vals[17][keep])
