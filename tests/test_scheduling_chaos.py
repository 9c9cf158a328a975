"""Chaos leg (DESIGN.md §7.3, CI `scheduling` job): inject a slow shard
replica into a 2x2 cluster and prove the scheduling layer keeps the SLO
green — hedging outruns the straggler so the answer is complete
(partial=False) and bit-identical, and partial gather caps the damage
when hedging is off."""
import time

import numpy as np
import pytest

from repro.cluster import FlashClusterSession, build_sharded_store
from repro.configs.paper_search import smoke
from repro.core import corpus as corpus_lib
from repro.obs import MetricsRegistry, Obs
from repro.serve import HedgePolicy, Query, QueryOptions
from repro.storage import FlashSearchSession, FlashStore
from repro.storage.store import _corpus_docs

SLOW_S = 0.5            # injected straggler delay
SLO_MS = 400.0          # the budget a query must stay under


class _Slow:
    """Sleep-wrapped shard-replica session: the injected straggler."""

    def __init__(self, inner, delay_s):
        self._inner = inner
        self._delay = delay_s

    def search(self, *a, **k):
        time.sleep(self._delay)
        return self._inner.search(*a, **k)

    def __getattr__(self, name):
        return getattr(self._inner, name)


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    cfg = smoke()
    corpus = corpus_lib.synthesize(160, cfg.vocab_size, cfg.avg_nnz_per_doc,
                                   cfg.nnz_pad, seed=17)
    docs = _corpus_docs(corpus)
    tmp = tmp_path_factory.mktemp("chaos")
    cl = build_sharded_store(str(tmp / "c2x2"), docs, n_shards=2,
                             replicas=2, policy="hash",
                             vocab_size=cfg.vocab_size, docs_per_segment=16)
    # a registry of its own: the hedge timer reads the process's shard
    # latency window otherwise, where other tests' cold compiles land
    sess = FlashClusterSession(
        cl, cfg, obs=Obs(registry=MetricsRegistry()),
        hedge_policy=HedgePolicy(percentile=0.95, min_ms=1.0,
                                 fallback_ms=30.0))
    union = FlashStore.create(str(tmp / "u"), vocab_size=cfg.vocab_size,
                              docs_per_segment=64)
    union.append_docs(docs)
    ref = FlashSearchSession(union, cfg)
    # warm every replica (open + compile) with DIRECT shard-session
    # calls — these bypass the router so they never reach the
    # cluster_shard_ms window — then seed that window with router-level
    # queries that are all-warm. The hedge timer is a percentile of the
    # window, and a cold-compile outlier from a first router query
    # would push the hedge past the deadline budget on a loaded machine
    wi, wv = corpus_lib.make_query(corpus, 0, cfg.max_query_nnz)
    wq = Query(wi[None], wv[None])
    for s in range(2):
        for r in range(2):
            sess.router._session(s, r).search_typed(wq)
    for _ in range(3):
        sess.search_typed(wq)
    yield cfg, corpus, sess, ref
    sess.close()
    ref.close()


def test_chaos_hedging_keeps_slo_green_and_complete(cluster):
    """The headline chaos assertion: with a replica stuck for SLOW_S,
    hedging wins the race — every query completes under the SLO with a
    FULL (partial=False) bit-identical answer."""
    cfg, corpus, sess, ref = cluster
    qs = [corpus_lib.make_query(corpus, i, cfg.max_query_nnz)
          for i in (3, 41, 77)]
    # every replica is already open + warm (module fixture)
    sess.router._sessions[1][0] = _Slow(sess.router._sessions[1][0], SLOW_S)
    try:
        for qi, qv in qs:
            q = Query(qi[None], qv[None])
            expect = ref.search_typed(Query(qi[None], qv[None]))
            t0 = time.monotonic()
            resp = sess.search(q, options=QueryOptions(
                deadline_ms=SLO_MS, allow_partial=True))
            wall_ms = (time.monotonic() - t0) * 1e3
            # SLO green: hedging won, so the answer is complete AND fast
            assert not resp.stats.partial, \
                f"hedge should have beaten the straggler; missing " \
                f"{resp.stats.shards_missing}"
            assert resp.stats.hedged
            assert wall_ms < SLO_MS, f"query took {wall_ms:.0f}ms"
            np.testing.assert_array_equal(resp.doc_ids, expect.doc_ids)
            np.testing.assert_array_equal(resp.scores, expect.scores)
        st = sess.last_stats
        assert st.hedges >= 1 and st.hedge_wins >= 1
        # the slow replica is degraded, not dead: never marked down
        assert not sess.router._down[1][0]
    finally:
        # unwrap so later module-scope users see the healthy replica
        sess.router._sessions[1][0] = sess.router._sessions[1][0]._inner


def test_chaos_partial_gather_caps_damage_without_hedging(cluster):
    """Same straggler with hedging pinned off: the deadline-bound gather
    degrades to a flagged partial answer inside the budget instead of
    stalling for the straggler."""
    cfg, corpus, sess, ref = cluster
    qi, qv = corpus_lib.make_query(corpus, 19, cfg.max_query_nnz)
    q = Query(qi[None], qv[None])
    sess.search_typed(q)
    slow = _Slow(sess.router._sessions[1][0], SLOW_S)
    sess.router._sessions[1][0] = slow
    # replica 1 out of rotation: no fail-over target, no hedge target
    sess.router.mark_down(1, 1)
    try:
        t0 = time.monotonic()
        resp = sess.search(q, options=QueryOptions(
            deadline_ms=80.0, allow_partial=True, hedging=False))
        wall_ms = (time.monotonic() - t0) * 1e3
        assert resp.stats.partial and resp.stats.shards_missing == (1,)
        assert not resp.stats.hedged
        assert wall_ms < SLO_MS, f"partial gather took {wall_ms:.0f}ms"
        # bounded staleness, not garbage: what came back is shard 0's
        # true answer
        shard0 = sess.router._session(0, 0).search_typed(q)
        np.testing.assert_array_equal(resp.doc_ids, shard0.doc_ids)
    finally:
        sess.router._sessions[1][0] = slow._inner
        sess.router.reset_health()
