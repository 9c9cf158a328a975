"""Query planner + device slab cache (DESIGN.md §4): explicit plans,
cache-first scan order, warm-vs-cold bit-equivalence on every scoring
surface, byte-budget eviction, precise invalidation, and the idempotent
close satellites."""
import dataclasses

import numpy as np
import pytest

from repro.configs.paper_search import smoke
from repro.core import corpus as corpus_lib
from repro.core.engine import PatternSearchEngine
from repro.distributed.meshctx import single_device_ctx
from repro.storage import (FlashSearchSession, FlashStore, Planner,
                           Prefetcher, SlabCache)
from repro.storage.plan import SOURCE_CACHE, SOURCE_DISK
from repro.storage.slabcache import slab_nbytes
from repro.storage.store import _corpus_docs

CFG = smoke()


def _build_store(root, corpus, docs_per_segment=100):
    store = FlashStore.create(str(root), vocab_size=CFG.vocab_size,
                              docs_per_segment=docs_per_segment)
    store.append_corpus(corpus)
    return store


def _queries(corpus, idxs):
    qs = [corpus_lib.make_query(corpus, i, CFG.max_query_nnz) for i in idxs]
    return np.stack([q[0] for q in qs]), np.stack([q[1] for q in qs])


@pytest.fixture(scope="module")
def corpus():
    return corpus_lib.synthesize(400, CFG.vocab_size, CFG.avg_nnz_per_doc,
                                 CFG.nnz_pad, seed=11)


# ---------------------------------------------------------------------------
# planner
# ---------------------------------------------------------------------------
def test_plan_verdicts_and_cache_first_order(tmp_path, corpus):
    store = _build_store(tmp_path / "s", corpus)
    sess = FlashSearchSession(store, CFG)
    qi, qv = _queries(corpus, [7])
    plan = sess._planner.plan(store, qi)
    # cold: every surviving segment is a disk step, none skipped for a
    # real document's own words
    assert plan.segments_total == store.n_segments
    assert plan.n_cached == 0 and plan.n_disk == len(plan.steps)
    assert len(plan.steps) + len(plan.skipped) == plan.segments_total
    sess.search(qi, qv)                      # populate the cache
    plan2 = sess._planner.plan(store, qi)
    assert plan2.n_cached == len(plan2.steps) > 0
    # scan order is cache-first by construction: once any step is a
    # disk step, no later step may be a cache hit
    sess.slab_cache.clear()
    sess.search(qi, qv)
    first = plan2.steps[0].name
    sess.slab_cache.invalidate(store.cache_token, [first])
    plan3 = sess._planner.plan(store, qi)
    sources = [s.source for s in plan3.steps]
    assert sources == sorted(sources)        # "cache" < "disk" lexically
    assert plan3.steps[-1].name == first and sources[-1] == SOURCE_DISK
    assert all(s == SOURCE_CACHE for s in sources[:-1])
    sess.close()


def test_plan_executes_through_every_source(tmp_path, corpus):
    """A mixed cache/disk plan scores bit-identically to the resident
    engine (the planner's ordering permutes the slab stream; the
    cross-slab merge is order-independent for distinct doc ids)."""
    store = _build_store(tmp_path / "s", corpus)
    eng = PatternSearchEngine(corpus, CFG, single_device_ctx())
    sess = FlashSearchSession(store, CFG)
    qi, qv = _queries(corpus, [3, 250])
    cold = sess.search(qi, qv)
    # knock half the entries out so the next plan mixes sources
    names = [k[1] for k in sess.slab_cache.keys()]
    sess.slab_cache.invalidate(store.cache_token, names[::2])
    mixed = sess.search(qi, qv)
    st = sess.last_stats
    assert st.cache_hits > 0 and st.cache_misses > 0
    ref = eng.search(qi, qv)
    for got in (cold, mixed):
        np.testing.assert_array_equal(got.doc_ids, ref.doc_ids)
        np.testing.assert_allclose(got.scores, ref.scores,
                                   rtol=1e-5, atol=1e-6)
    sess.close()


# ---------------------------------------------------------------------------
# warm vs cold bit-equivalence, per scoring surface
# ---------------------------------------------------------------------------
def test_warm_equals_cold_single_store(tmp_path, corpus):
    store = _build_store(tmp_path / "s", corpus)
    sess = FlashSearchSession(store, CFG)
    qi, qv = _queries(corpus, [0, 123, 399])
    cold = sess.search(qi, qv)
    cold_stats = sess.last_stats
    assert cold_stats.cache_hits == 0
    assert cold_stats.cache_misses == cold_stats.segments_scored > 0
    warm = sess.search(qi, qv)
    warm_stats = sess.last_stats
    assert warm_stats.cache_hits == warm_stats.segments_scored
    assert warm_stats.cache_misses == 0
    assert warm_stats.cache_hit_rate == 1.0
    np.testing.assert_array_equal(cold.doc_ids, warm.doc_ids)
    np.testing.assert_array_equal(cold.scores, warm.scores)
    # stats must be value-identical too: docs/truncations recorded in
    # the cache entry, not re-derived
    assert warm_stats.docs_scored == cold_stats.docs_scored
    assert warm_stats.pairs_truncated == cold_stats.pairs_truncated
    sess.close()


def test_warm_equals_cold_ingest_snapshot(tmp_path, corpus):
    """The live surface: base segments + sealed deltas + memtable, warm
    vs cold vs a from-scratch reference store."""
    docs = _corpus_docs(corpus)
    base, extra = docs[:300], docs[300:]
    store = _build_store(tmp_path / "live", corpus.slice_rows(0, 300),
                         docs_per_segment=64)
    sess = FlashSearchSession(store, CFG)
    sess.enable_ingest(seal_docs=40, auto_compact=False)
    for d, p in extra[:60]:
        sess.append(d, p)                    # forces one seal + a tail
    qi, qv = _queries(corpus, [5, 320])
    cold = sess.search(qi, qv)
    warm = sess.search(qi, qv)
    assert sess.last_stats.cache_hits > 0
    assert sess.last_stats.memtable_docs == 60 % 40
    np.testing.assert_array_equal(cold.doc_ids, warm.doc_ids)
    np.testing.assert_array_equal(cold.scores, warm.scores)
    ref_store = _build_store(tmp_path / "ref",
                             corpus.slice_rows(0, 360), docs_per_segment=64)
    with FlashSearchSession(ref_store, CFG) as ref:
        want = ref.search(qi, qv)
    np.testing.assert_array_equal(warm.doc_ids, want.doc_ids)
    np.testing.assert_array_equal(warm.scores, want.scores)
    # a fold must not poison the warm path: compact, then re-verify
    sess.flush_ingest()
    sess.ingest.compact_once()
    after = sess.search(qi, qv)
    np.testing.assert_array_equal(after.doc_ids, want.doc_ids)
    np.testing.assert_array_equal(after.scores, want.scores)
    sess.close()


def test_warm_equals_cold_cluster(tmp_path, corpus):
    from repro.cluster import FlashClusterSession, build_sharded_store
    docs = _corpus_docs(corpus)
    croot = str(tmp_path / "cluster")
    build_sharded_store(croot, docs, n_shards=3, replicas=1,
                        vocab_size=CFG.vocab_size, docs_per_segment=64)
    qi, qv = _queries(corpus, [9, 200, 377])
    with FlashClusterSession(croot, CFG) as cs:
        cold = cs.search(qi, qv)
        assert cs.last_stats.cache_hits == 0
        warm = cs.search(qi, qv)
        agg = cs.last_stats
        # aggregated through the scatter/gather path across all shards
        assert agg.cache_hits == agg.segments_scored > 0
        assert agg.cache_misses == 0 and agg.cache_hit_rate == 1.0
        assert cs.cache_stats.hits >= agg.cache_hits
        np.testing.assert_array_equal(cold.doc_ids, warm.doc_ids)
        np.testing.assert_array_equal(cold.scores, warm.scores)
        # each shard session draws on its device's cache; on the one
        # CPU device that is ONE cache instance + byte budget for all
        router = cs.router
        shard_sessions = router._open_sessions()
        assert len(shard_sessions) == 3
        assert all(router._sessions[s][0].slab_cache
                   is cs.slab_caches[router.device_of(s, 0)]
                   for s in range(3))
        assert len(cs.slab_caches) == 1
        assert len({id(s.slab_cache) for s in shard_sessions}) == 1


def test_warm_equals_cold_service_submit(tmp_path, corpus):
    """The micro-batched surface: coalesced submits execute through the
    same planner/cache and warm hits stay bit-identical."""
    store = _build_store(tmp_path / "s", corpus)
    sess = FlashSearchSession(store, CFG)
    qi, qv = corpus_lib.make_query(corpus, 77, CFG.max_query_nnz)
    first = sess.submit(qi, qv).result()
    again = sess.submit(qi, qv).result()
    assert sess.last_stats.cache_hits > 0
    np.testing.assert_array_equal(first.doc_ids, again.doc_ids)
    np.testing.assert_array_equal(first.scores, again.scores)
    assert sess.cache_stats.hits > 0
    sess.close()


# ---------------------------------------------------------------------------
# budget, eviction, invalidation
# ---------------------------------------------------------------------------
def test_eviction_under_tiny_budget(tmp_path, corpus):
    store = _build_store(tmp_path / "s", corpus)
    eng = PatternSearchEngine(corpus, CFG, single_device_ctx())
    # budget fits ~2 slabs: steady state must evict yet stay correct
    probe = FlashSearchSession(store, CFG)
    qi, qv = _queries(corpus, [50])
    probe.search(qi, qv)
    one_slab = max(e.nbytes for e in probe.slab_cache._entries.values())
    probe.close()
    store2 = FlashStore.open(str(tmp_path / "s"))
    sess = FlashSearchSession(store2, CFG,
                              cache_bytes=int(one_slab * 2.5))
    for _ in range(3):
        got = sess.search(qi, qv)
    st = sess.last_stats
    assert sess.slab_cache.stats.evictions > 0
    assert sess.slab_cache.nbytes <= sess.slab_cache.max_bytes
    assert len(sess.slab_cache) <= 2
    ref = eng.search(qi, qv)
    np.testing.assert_array_equal(got.doc_ids, ref.doc_ids)
    np.testing.assert_allclose(got.scores, ref.scores, rtol=1e-5, atol=1e-6)
    # with fewer resident slabs than survivors there are hits AND misses
    assert st.cache_misses > 0
    sess.close()


def test_slab_larger_than_budget_not_admitted(tmp_path, corpus):
    store = _build_store(tmp_path / "s", corpus)
    sess = FlashSearchSession(store, CFG, cache_bytes=64)   # absurd budget
    qi, qv = _queries(corpus, [50])
    r1 = sess.search(qi, qv)
    r2 = sess.search(qi, qv)
    assert len(sess.slab_cache) == 0 and sess.slab_cache.nbytes == 0
    assert sess.last_stats.cache_hits == 0
    np.testing.assert_array_equal(r1.doc_ids, r2.doc_ids)
    sess.close()


def test_cache_disabled(tmp_path, corpus):
    store = _build_store(tmp_path / "s", corpus)
    sess = FlashSearchSession(store, CFG, cache_bytes=0)
    assert sess.slab_cache is None and sess.cache_stats is None
    qi, qv = _queries(corpus, [1])
    sess.search(qi, qv)
    sess.search(qi, qv)
    st = sess.last_stats
    assert st.cache_hits == st.cache_misses == st.cache_evictions == 0
    assert st.cache_hit_rate == 0.0
    sess.close()


def test_compact_invalidates_replaced_names(tmp_path, corpus):
    """FlashStore.compact rewrites every segment: the cache must drop
    exactly the replaced names (generation-precise invalidation), and
    the next search must re-decode the new files, not serve stale slabs."""
    store = _build_store(tmp_path / "s", corpus.slice_rows(0, 130),
                         docs_per_segment=40)   # 4 segments, last underfull
    eng = PatternSearchEngine(corpus.slice_rows(0, 130), CFG,
                              single_device_ctx())
    sess = FlashSearchSession(store, CFG)
    qi, qv = _queries(corpus, [10])
    sess.search(qi, qv)
    assert len(sess.slab_cache) > 0
    gen = store.generation
    store.compact()
    assert store.generation == gen + 1
    assert len(sess.slab_cache) == 0            # all old names replaced
    assert sess.slab_cache.stats.invalidations > 0
    got = sess.search(qi, qv)
    assert sess.last_stats.cache_hits == 0      # nothing stale served
    ref = eng.search(qi, qv)
    np.testing.assert_array_equal(got.doc_ids, ref.doc_ids)
    np.testing.assert_allclose(got.scores, ref.scores, rtol=1e-5, atol=1e-6)
    sess.close()


def test_shared_cache_across_sessions(tmp_path, corpus):
    """'Across queries, sessions, and micro-batches': a second session
    over the same store instance warms up from the first one's work."""
    store = _build_store(tmp_path / "s", corpus)
    shared = SlabCache()
    qi, qv = _queries(corpus, [42])
    s1 = FlashSearchSession(store, CFG, slab_cache=shared)
    r1 = s1.search(qi, qv)
    s2 = FlashSearchSession(store, CFG, slab_cache=shared)
    r2 = s2.search(qi, qv)
    assert s2.last_stats.cache_hits == s2.last_stats.segments_scored > 0
    np.testing.assert_array_equal(r1.doc_ids, r2.doc_ids)
    np.testing.assert_array_equal(r1.scores, r2.scores)
    # sessions share lifetime stats through the one cache object;
    # cache_stats returns a locked *snapshot* (not the live mutating
    # dataclass), so shared state is proven by value, and the snapshot
    # must be detached from subsequent cache activity
    snap = s1.cache_stats
    assert snap == s2.cache_stats
    assert snap is not shared.stats
    shared.stats.hits += 1
    try:
        assert s1.cache_stats.hits == snap.hits + 1  # live counters moved
        assert snap == dataclasses.replace(snap)     # snapshot did not
    finally:
        shared.stats.hits -= 1
    # registrations are refcounted: closing one session must neither
    # stop the store's invalidations for the survivor nor wipe the
    # survivor's warm set
    s1.close()
    assert store._caches
    assert len(shared) > 0
    r3 = s2.search(qi, qv)
    assert s2.last_stats.cache_hits == s2.last_stats.segments_scored > 0
    np.testing.assert_array_equal(r3.doc_ids, r1.doc_ids)
    s2.close()
    assert not store._caches
    assert len(shared) == 0


def test_reopened_store_cannot_alias_cache_entries(tmp_path, corpus):
    """Distinct FlashStore instances get distinct cache tokens, so a
    crash-reopened store (which may reuse segment *names* on disk) can
    never be served another instance's slabs."""
    store1 = _build_store(tmp_path / "s", corpus)
    shared = SlabCache()
    s1 = FlashSearchSession(store1, CFG, slab_cache=shared)
    qi, qv = _queries(corpus, [8])
    s1.search(qi, qv)
    store2 = FlashStore.open(str(tmp_path / "s"))
    assert store2.cache_token != store1.cache_token
    s2 = FlashSearchSession(store2, CFG, slab_cache=shared)
    s2.search(qi, qv)
    assert s2.last_stats.cache_hits == 0        # token mismatch = miss
    s2.close()
    s1.close()


def test_nbytes_accounting_matches_slabs(tmp_path, corpus):
    store = _build_store(tmp_path / "s", corpus)
    sess = FlashSearchSession(store, CFG)
    qi, qv = _queries(corpus, [3])
    sess.search(qi, qv)
    cache = sess.slab_cache
    assert cache.nbytes == sum(e.nbytes for e in cache._entries.values())
    assert all(e.nbytes == slab_nbytes(e.slab)
               for e in cache._entries.values())
    sess.close()
    # session close drops the store's entries from the cache
    assert len(cache) == 0 and cache.nbytes == 0


def test_partial_warm_tiebreak_matches_cold(tmp_path):
    """Two byte-identical documents in different segments score exactly
    equal; the merge breaks ties by fold position. A partially warm
    plan scans the cached segment *first* but must still fold in
    manifest order, so the cold scan's winner keeps winning no matter
    which segments happen to be resident."""
    pairs = [(3, 2), (7, 1)]
    docs = []
    for i in range(30):
        if i in (5, 25):
            docs.append((i, pairs))             # the tied twins
        else:
            docs.append((i, [(100 + i, 1)]))    # filler, filtered out
    store = FlashStore.create(str(tmp_path / "tie"),
                              vocab_size=CFG.vocab_size,
                              docs_per_segment=10)
    store.append_docs(docs)
    sess = FlashSearchSession(store, CFG)
    qi = np.full((1, CFG.max_query_nnz), -1, np.int32)
    qv = np.zeros((1, CFG.max_query_nnz), np.float32)
    for j, (w, c) in enumerate(pairs):
        qi[0, j] = w
        qv[0, j] = c
    cold = sess.search(qi, qv)
    assert sess.last_stats.segments_scored == 2       # filler seg skipped
    assert cold.doc_ids[0, 0] == 5                    # manifest-first wins
    assert cold.scores[0, 0] == cold.scores[0, 1]     # genuinely tied
    # leave only the LATER segment resident: the plan now scans it first
    first_seg = store.entries[0].name
    sess.slab_cache.invalidate(store.cache_token, [first_seg])
    partial = sess.search(qi, qv)
    st = sess.last_stats
    assert st.cache_hits == 1 and st.cache_misses == 1
    np.testing.assert_array_equal(partial.doc_ids, cold.doc_ids)
    np.testing.assert_array_equal(partial.scores, cold.scores)
    sess.close()


def test_admission_gated_on_plan_generation(tmp_path, corpus):
    """A plan outlived by a manifest mutation must not admit its slabs:
    they may be graveyard files the mutation just invalidated, and
    re-admitting would undo the precise invalidation."""
    from repro.storage import plan as plan_lib
    from repro.storage.session import SearchStats

    store = _build_store(tmp_path / "s", corpus)
    sess = FlashSearchSession(store, CFG)
    qi, qv = _queries(corpus, [12])
    plan = sess._planner.plan(store, qi)
    store.bump_generation()                  # a fold/compact commits
    stats = SearchStats(segments_total=plan.segments_total,
                        segments_skipped=len(plan.skipped),
                        segments_scored=len(plan.steps))
    plan_lib.execute_plan(sess.engine, store, plan, qi, qv, stats=stats,
                          cache=sess.slab_cache)
    assert len(sess.slab_cache) == 0         # nothing admitted
    # a fresh plan at the live generation admits again
    got = sess.search(qi, qv)
    assert len(sess.slab_cache) > 0
    ref = PatternSearchEngine(corpus, CFG, single_device_ctx()).search(qi, qv)
    np.testing.assert_array_equal(got.doc_ids, ref.doc_ids)
    sess.close()


def test_snapshot_outlived_by_fold_never_readmits(tmp_path, corpus):
    """The racy interleaving: capture -> fold commits (precise
    invalidation) -> the straggling snapshot plans and scores. Its
    graveyard slabs must not be admitted back into the cache — the
    plan's capture-time generation no longer matches the live one."""
    store = _build_store(tmp_path / "live", corpus.slice_rows(0, 200),
                         docs_per_segment=16)
    sess = FlashSearchSession(store, CFG)
    pipe = sess.enable_ingest(seal_docs=8, fold_min_segments=2,
                              auto_compact=False)
    for d, p in _corpus_docs(corpus)[200:230]:
        sess.append(d, p)
    sess.flush_ingest()
    snap = pipe.capture()
    assert pipe.compact_once() > 0           # fold lands mid-"query"
    assert snap.generation != snap.live_generation
    qi, qv = _queries(corpus, [3, 210])
    got = sess._search_view(snap, snap, qi, qv)
    snap.close()
    assert len(sess.slab_cache) == 0         # stale plan admitted nothing
    fresh = sess.search(qi, qv)              # live plan admits + agrees
    assert len(sess.slab_cache) > 0
    np.testing.assert_array_equal(got.doc_ids, fresh.doc_ids)
    np.testing.assert_array_equal(got.scores, fresh.scores)
    sess.close()


# ---------------------------------------------------------------------------
# idempotent close satellites
# ---------------------------------------------------------------------------
def test_prefetcher_close_idempotent_with_unconsumed_items():
    loaded = []

    def load(i):
        loaded.append(i)
        return i * i

    pf = Prefetcher(range(16), load, depth=2)
    assert next(iter(pf)) == 0               # consume one, abandon rest
    pf.close()
    worker = pf._worker
    assert not worker.is_alive()             # no leaked thread
    pf.close()                               # second close: no-op
    pf.close()
    assert not worker.is_alive()
    assert len(loaded) <= 4                  # backpressure held


def test_session_close_idempotent(tmp_path, corpus):
    store = _build_store(tmp_path / "s", corpus)
    sess = FlashSearchSession(store, CFG)
    sess.enable_ingest(seal_docs=1000, auto_compact=False)
    qi, qv = _queries(corpus, [1])
    sess.search(qi, qv)
    sess.close()
    sess.close()                             # must not double-free
    assert not store._caches                 # registration detached once
    with pytest.raises(RuntimeError):
        sess.service()


def test_snapshot_close_idempotent_no_graveyard_double_drain(tmp_path,
                                                             corpus):
    """Closing one snapshot twice must not decrement the live-snapshot
    count twice — that would drain the graveyard under a *different*
    still-open snapshot and delete files it may score."""
    store = _build_store(tmp_path / "live", corpus.slice_rows(0, 200),
                         docs_per_segment=16)
    sess = FlashSearchSession(store, CFG)
    pipe = sess.enable_ingest(seal_docs=8, fold_min_segments=2,
                              auto_compact=False)
    for d, p in _corpus_docs(corpus)[200:230]:
        sess.append(d, p)
    sess.flush_ingest()
    snap_a = pipe.capture()
    snap_b = pipe.capture()
    snap_a.close()
    snap_a.close()                           # idempotent: count stays 1
    assert pipe._live_snapshots == 1
    folded = pipe.compact_once()             # parks replaced files
    assert folded > 0
    assert pipe._graveyard                   # deferred while b lives
    for e in snap_b.entries:                 # every captured file opens
        snap_b.segment(e.name).close()
    snap_b.close()
    assert pipe._live_snapshots == 0
    assert not pipe._graveyard               # drained exactly once
    sess.close()
