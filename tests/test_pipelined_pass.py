"""The pipelined pass (DESIGN.md §4.1): ``execute_plan`` prepares a
batch's query once, dispatches each slab's program without waiting for
it, and brings every slab's top-k back in one copy. Its answers and
``SearchStats`` must be bit-identical to scoring slab by slab, each slab
waited on through ``search_streaming`` before the next is dispatched, and
folded in manifest rank order; and a pass over S slabs observes S
dispatches, one prepare and one wait."""
import numpy as np
import pytest

from repro.configs.paper_search import smoke
from repro.core.engine import _merge_results
from repro.obs import Obs
from repro.serve.api import Query, QueryOptions
from repro.storage import FlashSearchSession, FlashStore
from repro.storage.postings import PostingIndex

CFG = smoke()
SEGMENTS = 6
DOCS_PER_SEGMENT = 40
WORDS_PER_SEGMENT = 60       # each segment's docs draw from their own words
STATS = ("cache_hits", "cache_misses", "docs_scored", "pairs_truncated",
         "filter_fp_segments")
SLAB_STAGES = ("slab_prep", "slab_dispatch", "slab_wait")


def _docs(seed=5):
    """Docs whose words come from their segment's own range, so a query
    built from one segment's doc has no term in most others."""
    rng = np.random.default_rng(seed)
    docs = []
    for d in range(SEGMENTS * DOCS_PER_SEGMENT):
        lo = (d // DOCS_PER_SEGMENT) * WORDS_PER_SEGMENT
        words = rng.choice(WORDS_PER_SEGMENT, 9, replace=False) + lo
        docs.append((d, [(int(w), int(c)) for w, c in
                         zip(words, rng.integers(1, 6, words.size))]))
    return docs


DOCS = _docs()
BATCHES = ((3, 50, 201), (7,), (3, 50, 201), (120, 239, 11, 88))


def _batch(docs):
    """Each query is its doc's own bag, [L, Qn], -1 / 0 padded."""
    ids = np.full((len(docs), CFG.max_query_nnz), -1, np.int32)
    vals = np.zeros((len(docs), CFG.max_query_nnz), np.float32)
    for row, d in enumerate(docs):
        pairs = DOCS[d][1]
        ids[row, :len(pairs)] = [w for w, _ in pairs]
        vals[row, :len(pairs)] = [c for _, c in pairs]
    return Query(ids, vals)


class SlabBySlab:
    """The session engine as the scan used it before the pass was
    pipelined: every slab is scored to its host top-k by
    ``search_streaming`` (prepare, dispatch, wait) when it is dispatched,
    so the host waits on the device between slabs. Keeps each slab's
    result."""

    def __init__(self, engine):
        self._engine = engine
        self.results = []

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def prepare(self, q_ids, q_vals):
        return q_ids, q_vals

    def dispatch(self, q, slab):
        self.results.append(self._engine.search_streaming(*q, [slab]))
        return self.results[-1]

    def collect(self, results, q_ids, q_vals):
        return list(results)


def _empty_pools_without_overlap(monkeypatch):
    """A segment with no query term gives an empty candidate pool (the
    posting index itself keeps zero-score docs, so only an empty segment
    would): the executor then scores no slab for it."""
    real = PostingIndex.candidates

    def candidates(self, q_ids, q_vals, n_cand):
        if not np.isin(q_ids[q_ids >= 0], self.term_ids).any():
            return np.empty(0, np.int64)
        return real(self, q_ids, q_vals, n_cand)
    monkeypatch.setattr(PostingIndex, "candidates", candidates)


def _session(root, backend, case, obs, slab_bytes=None):
    kw = dict(backend=backend, obs=obs)
    if case == "half_cached":
        kw["cache_bytes"] = SEGMENTS // 2 * slab_bytes
    if case == "approx":
        # no filter and no cache, so every segment takes the posting path
        kw.update(cache_bytes=0, use_filter=False, mode="approx",
                  candidates=8)
    store = FlashStore.create(str(root), vocab_size=CFG.vocab_size,
                              docs_per_segment=DOCS_PER_SEGMENT)
    n_base = len(DOCS) - (DOCS_PER_SEGMENT // 2 if case == "memtable"
                          else 0)
    store.append_docs(DOCS[:n_base])
    sess = FlashSearchSession(store, CFG, **kw)
    if case == "memtable":
        # the last half segment stays in the memtable: no seal
        sess.enable_ingest(seal_docs=10 * DOCS_PER_SEGMENT,
                           auto_compact=False)
        for d, pairs in DOCS[n_base:]:
            sess.append(d, pairs)
    return sess


def _slab_bytes(tmp_path):
    probe = _session(tmp_path / "probe", "jnp", "cached", Obs.disabled())
    probe.search_typed(_batch((0,)))
    n = probe.slab_cache.nbytes // len(probe.slab_cache)
    probe.close()
    return n


def _rank_fold(results):
    """Fold per-slab results in manifest rank order, memtable last: docs
    are stored in id order, so a slab's rank is the order of its ids."""
    best = None
    for r in sorted(results, key=lambda r: r.doc_ids[r.doc_ids >= 0].min()):
        best = r if best is None else _merge_results(best, r, CFG.top_k)
    return best


def _counts(obs):
    return {s: obs.registry.histogram("stage_ms", stage=s).state().total
            for s in SLAB_STAGES}


@pytest.mark.parametrize("case,backend", [
    ("cached", "jnp"),
    ("half_cached", "jnp"),
    ("half_cached", "pallas"),
    ("half_cached", "pallas_packed"),
    ("half_cached", "pallas_fused"),
    ("memtable", "jnp"),
    ("approx", "jnp"),
])
def test_pipelined_pass_equals_slab_by_slab(case, backend, tmp_path,
                                            monkeypatch):
    if case == "approx":
        _empty_pools_without_overlap(monkeypatch)
    slab_bytes = _slab_bytes(tmp_path) if case == "half_cached" else None
    obs = Obs()
    got = _session(tmp_path / "pipelined", backend, case, obs, slab_bytes)
    want = _session(tmp_path / "slab_by_slab", backend, case, Obs(),
                    slab_bytes)
    ref = want.engine = SlabBySlab(want.engine)
    seen = set()
    for docs in BATCHES:
        q = _batch(docs)
        before, slabs0 = _counts(obs), len(ref.results)
        a, b = got.search_typed(q), want.search_typed(q)
        c = _rank_fold(ref.results[slabs0:])
        for r in (b, c):
            np.testing.assert_array_equal(a.doc_ids, r.doc_ids)
            np.testing.assert_array_equal(a.scores, r.scores)
        sa, sb = got.last_stats, want.last_stats
        assert {k: getattr(sa, k) for k in STATS} == {
            k: getattr(sb, k) for k in STATS}
        after = _counts(obs)
        n_slabs = len(ref.results) - slabs0
        assert n_slabs >= 1
        assert after["slab_dispatch"] - before["slab_dispatch"] == n_slabs
        assert after["slab_prep"] - before["slab_prep"] == 1
        assert after["slab_wait"] - before["slab_wait"] == 1
        seen.add((sa.cache_hits > 0, sa.cache_misses > 0,
                  sa.memtable_docs > 0,
                  sa.approx_segments > n_slabs - (sa.memtable_docs > 0)))
    # the batches went through what the case is for: a warm store, a
    # store half in the cache, a memtable tail, empty candidate pools
    expect = {"cached": (True, False, False, False),
              "half_cached": (True, True, False, False),
              "memtable": (True, False, True, False),
              "approx": (False, False, False, True)}[case]
    assert expect in seen, seen
    got.close()
    want.close()


def test_per_query_options_take_one_pass(tmp_path):
    """A per-query approx override runs the same one-prepare, one-wait
    pass, with answers equal to the slab-by-slab scan's."""
    obs = Obs()
    got = _session(tmp_path / "p", "jnp", "cached", obs)
    want = _session(tmp_path / "s", "jnp", "cached", Obs())
    ref = want.engine = SlabBySlab(want.engine)
    opts = QueryOptions(mode="approx", candidates=len(DOCS))
    for docs in BATCHES[:2]:
        a = got.search(_batch(docs), options=opts)
        b = want.search(_batch(docs), options=opts)
        np.testing.assert_array_equal(a.doc_ids, b.doc_ids)
        np.testing.assert_array_equal(a.scores, b.scores)
    c = _counts(obs)
    assert c["slab_prep"] == c["slab_wait"] == 2
    assert c["slab_dispatch"] == len(ref.results) > 2
    got.close()
    want.close()
