"""A bag-of-words corpus in the shape of a UCI "Bag of Words" data set,
made from a seed.

A vectorized copy of the program's synthesizer (``repro.core.corpus.
synthesize``, the paper's §IV.A method): documents are permutations of
topic word sets drawn with Zipf word frequencies, with a random add/remove
step. It departs from that synthesizer where the source's own numbers say
otherwise, and the configuration lists each departure under ``assumed``:

- a topic word set holds ``topic_words`` *distinct* Zipf draws, so a
  document keeps the Poisson(``nnz_per_doc``) length the source's NNZ / D
  gives (drawn with repeats, as the program does, a third of the terms
  collapse into duplicates);
- term counts are Geometric with mean ``count_per_nnz`` (the source's
  N / NNZ), not uniform on 1..29.

Every document has 1..``nnz_pad`` distinct terms, so no pair is ever
truncated at ``nnz_pad``.
"""
from __future__ import annotations

import dataclasses

import numpy as np

_CHUNK = 1 << 16            # documents drawn per vectorized step


@dataclasses.dataclass
class BowCorpus:
    ids: np.ndarray         # [n, nnz_pad] int32, sorted per row, -1 padded
    vals: np.ndarray        # [n, nnz_pad] float32 term counts, 0 padded

    @property
    def n_docs(self) -> int:
        return int(self.ids.shape[0])

    def row(self, i: int):
        """Document ``i`` as its (ids, counts) bag without padding."""
        keep = self.ids[i] >= 0
        return self.ids[i][keep], self.vals[i][keep]

    def docs(self, lo: int, hi: int):
        """Rows [lo, hi) as ``[(doc_id, [(word, count), ...])]``, the form
        ``FlashStore.append_docs`` takes; the doc id is the row index."""
        out = []
        for r in range(lo, hi):
            keep = self.ids[r] >= 0
            out.append((r, list(zip(self.ids[r][keep].tolist(),
                                    self.vals[r][keep].astype(int).tolist()))))
        return out


def _topics(rng, n_topics: int, topic_words: int, vocab: int,
            zipf: float) -> np.ndarray:
    """[n_topics, topic_words] distinct word ids per topic: the first
    ``topic_words`` distinct values of ``2 * topic_words`` Zipf draws, in
    draw order."""
    draws = rng.zipf(zipf, size=(n_topics, 2 * topic_words)) % vocab
    order = np.argsort(draws, axis=1, kind="stable")
    ranked = np.take_along_axis(draws, order, axis=1)
    first = np.ones(draws.shape, bool)
    first[:, 1:] = ranked[:, 1:] != ranked[:, :-1]
    is_first = np.zeros(draws.shape, bool)
    np.put_along_axis(is_first, order, first, axis=1)
    keep = is_first & (np.cumsum(is_first, axis=1) <= topic_words)
    if not np.all(keep.sum(1) == topic_words):
        raise ValueError("a topic drew fewer than topic_words distinct words")
    return draws[keep].reshape(n_topics, topic_words).astype(np.int64)


def generate(cfg: dict, seed: int) -> BowCorpus:
    """The configuration's corpus: ``n_docs`` rows at ``nnz_pad`` width."""
    n = int(cfg["n_docs"])
    vocab = int(cfg["vocab_size"])
    pad = int(cfg["nnz_pad"])
    tw = int(cfg["topic_words"])
    if tw < pad:
        raise ValueError("topic_words must be at least nnz_pad")
    rng = np.random.default_rng(seed)
    lens = np.clip(rng.poisson(cfg["nnz_per_doc"], n), 1, pad)
    n_topics = max(1, n // int(cfg["docs_per_topic"]))
    topics = _topics(rng, n_topics, tw, vocab, float(cfg["zipf"]))
    doc_topic = rng.integers(n_topics, size=n)
    ids = np.empty((n, pad), np.int32)
    pos = np.arange(tw)
    mut_w = max(1, pad // 8)
    for lo in range(0, n, _CHUNK):
        hi = min(n, lo + _CHUNK)
        c = hi - lo
        take = lens[lo:hi, None]
        # a random permutation of the topic's words per document
        perm = np.argsort(rng.random((c, tw), dtype=np.float32), axis=1)
        words = np.take_along_axis(topics[doc_topic[lo:hi]], perm, axis=1)
        # the add/remove step: the first max(1, take // 8) words replaced
        mut = rng.integers(0, vocab, size=(c, mut_w))
        n_mut = np.maximum(1, take // 8)
        words[:, :mut_w] = np.where(pos[None, :mut_w] < n_mut, mut,
                                    words[:, :mut_w])
        words = np.where(pos[None] < take, words, vocab)
        words = np.sort(words, axis=1)[:, :pad]
        dup = np.zeros(words.shape, bool)
        dup[:, 1:] = words[:, 1:] == words[:, :-1]
        words = np.sort(np.where(dup, vocab, words), axis=1)
        ids[lo:hi] = np.where(words == vocab, -1, words)
    real = ids >= 0
    vals = np.zeros((n, pad), np.float32)
    vals[real] = rng.geometric(1.0 / float(cfg["count_per_nnz"]),
                               size=int(real.sum()))
    return BowCorpus(ids, vals)


def more_like_this(corpus: BowCorpus, doc: int):
    """A "more like this" query: the stored document's own bag."""
    return corpus.row(doc)
