"""Replica hedging (DESIGN.md §7.3): run_hedged mechanics, the
telemetry-seeded HedgePolicy threshold, and end-to-end cluster hedging
— a straggling replica is outrun, results stay bit-identical, and slow
is never marked down."""
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from repro.cluster import FlashClusterSession, build_sharded_store
from repro.configs.paper_search import smoke
from repro.core import corpus as corpus_lib
from repro.obs import NULL_SPAN, MetricsRegistry, Obs
from repro.serve import (HedgePolicy, Query, QueryOptions, SpawnExecutor,
                         run_hedged)
from repro.storage import FlashSearchSession, FlashStore
from repro.storage.store import _corpus_docs


@pytest.fixture(scope="module")
def pool():
    with ThreadPoolExecutor(max_workers=4) as ex:
        yield ex


# ---------------------------------------------------------------------------
# run_hedged mechanics
# ---------------------------------------------------------------------------
def test_hedge_fast_primary_never_fires(pool):
    out = run_hedged([lambda: "fast", lambda: "never"], pool,
                     hedge_after_s=0.5)
    assert out.result == "fast"
    assert out.winner_index == 0
    assert out.hedges_fired == 0 and not out.hedge_won


def test_hedge_fires_and_wins_on_straggler(pool):
    fired = []

    def slow():
        time.sleep(0.5)
        return "slow"

    out = run_hedged([slow, lambda: "hedge"], pool, hedge_after_s=0.02,
                     on_hedge=fired.append)
    assert out.result == "hedge"
    assert out.winner_index == 1
    assert out.hedges_fired == 1 and out.hedge_won
    assert fired == [1]


def test_hedge_fires_but_loses_to_primary(pool):
    def primary():
        time.sleep(0.08)
        return "primary"

    def laggard():
        time.sleep(1.0)
        return "laggard"

    out = run_hedged([primary, laggard], pool, hedge_after_s=0.02)
    assert out.result == "primary"
    assert out.hedges_fired == 1 and not out.hedge_won   # fired, lost


def test_hedge_error_fires_next_attempt_immediately(pool):
    def boom():
        raise OSError("replica gone")

    t0 = time.monotonic()
    out = run_hedged([boom, lambda: "backup"], pool, hedge_after_s=5.0)
    assert out.result == "backup" and out.hedge_won
    # the error fired the hedge at once, not after the 5s straggler timer
    assert time.monotonic() - t0 < 2.0
    assert isinstance(out.errors[0], OSError)


def test_hedge_all_attempts_failed_raises_first_error(pool):
    def boom_a():
        raise OSError("a")

    def boom_b():
        raise ValueError("b")

    with pytest.raises(OSError, match="a"):
        run_hedged([boom_a, boom_b], pool, hedge_after_s=0.01)


def test_hedge_single_attempt_degenerates_to_plain_call(pool):
    assert run_hedged([lambda: 7], pool, hedge_after_s=0.001).result == 7
    with pytest.raises(ValueError):
        run_hedged([], pool, hedge_after_s=0.001)


def test_hedge_attempts_never_starve_behind_abandoned_losers():
    """Regression: back-to-back hedged calls against a persistent
    straggler. Query 1's abandoned loser is still sleeping (and holding
    the per-replica serialization lock) when query 2 arrives; query 2's
    primary attempt queues on that lock, so its hedge is the only path
    to an answer — it must *start* immediately when the timer fires,
    not wait for executor capacity held by the loser. On the old
    bounded 2-worker hedge pool this took the straggler's full 0.4 s."""
    ex = SpawnExecutor()
    replica0 = threading.Lock()   # per-replica serialization, as in the router

    def slow():
        with replica0:
            time.sleep(0.4)
            return "slow"

    out1 = run_hedged([slow, lambda: "fast"], ex, hedge_after_s=0.005)
    assert out1.result == "fast" and out1.hedge_won
    t0 = time.monotonic()
    out2 = run_hedged([slow, lambda: "fast"], ex, hedge_after_s=0.005)
    wall = time.monotonic() - t0
    assert out2.result == "fast" and out2.hedge_won
    assert wall < 0.2, f"hedge starved behind the abandoned loser: {wall:.3f}s"
    # shutdown joins the stragglers so nothing outlives the test
    ex.shutdown(wait=True)


# ---------------------------------------------------------------------------
# HedgePolicy: threshold seeded from the rolling-window histogram
# ---------------------------------------------------------------------------
def test_hedge_policy_reads_windowed_percentile():
    reg = MetricsRegistry()
    h = reg.histogram("cluster_shard_ms")
    for ms in (10.0,) * 19 + (200.0,):
        h.observe(ms)
    pol = HedgePolicy(percentile=0.5, min_ms=1.0, fallback_ms=999.0)
    thr = pol.hedge_after_ms(reg)
    assert 1.0 <= thr < 200.0               # seeded from data, not fallback
    assert thr != 999.0


def test_hedge_policy_falls_back_cold_and_floors():
    reg = MetricsRegistry()                 # histogram never observed
    pol = HedgePolicy(percentile=0.95, min_ms=5.0, fallback_ms=42.0)
    assert pol.hedge_after_ms(reg) == 42.0
    assert pol.hedge_after_ms(None) == 42.0
    # the floor wins over a uniformly-fast window
    reg2 = MetricsRegistry()
    h = reg2.histogram("cluster_shard_ms")
    for _ in range(50):
        h.observe(0.01)
    assert HedgePolicy(min_ms=5.0).hedge_after_ms(reg2) == 5.0


def test_hedge_policy_validates():
    with pytest.raises(ValueError):
        HedgePolicy(percentile=1.5)
    with pytest.raises(ValueError):
        HedgePolicy(fallback_ms=0.0)


# ---------------------------------------------------------------------------
# end-to-end: a slow replica is outrun, bit-identically, with no marks
# ---------------------------------------------------------------------------
class _Slow:
    """Wraps a shard-replica session with a fixed pre-search delay
    (the chaos injection: a stuck device, a compactor stall)."""

    def __init__(self, inner, delay_s):
        self._inner = inner
        self._delay = delay_s

    def search(self, *a, **k):
        time.sleep(self._delay)
        return self._inner.search(*a, **k)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def _warm_replicas(sess, q):
    """Open every replica's session and compile its program, so a hedge
    races the straggler, not a cold replica's first compile."""
    for s in range(sess.store.n_shards):
        for r in range(sess.store.replicas):
            sess.router._attempt(s, r, q, NULL_SPAN)


def _cluster(tmp_path, cfg, n_shards=2, replicas=2, **kw):
    corpus = corpus_lib.synthesize(120, cfg.vocab_size, cfg.avg_nnz_per_doc,
                                   cfg.nnz_pad, seed=11)
    docs = _corpus_docs(corpus)
    cl = build_sharded_store(str(tmp_path / "c"), docs, n_shards=n_shards,
                             replicas=replicas, policy="hash",
                             vocab_size=cfg.vocab_size, docs_per_segment=16)
    union = FlashStore.create(str(tmp_path / "u"),
                              vocab_size=cfg.vocab_size, docs_per_segment=64)
    union.append_docs(docs)
    sess = FlashClusterSession(cl, cfg, **kw)
    return corpus, sess, FlashSearchSession(union, cfg)


def test_hedge_outruns_slow_replica_bit_identically(tmp_path):
    cfg = smoke()
    # a registry of its own: the hedge threshold is a percentile of the
    # rolling shard-latency window, which must hold this cluster's warm
    # shard times, not cold compiles of other tests in the process
    corpus, sess, union = _cluster(
        tmp_path, cfg, obs=Obs(registry=MetricsRegistry()),
        hedge_policy=HedgePolicy(percentile=0.5, min_ms=1.0, fallback_ms=20.0))
    try:
        qi, qv = corpus_lib.make_query(corpus, 7, cfg.max_query_nnz)
        q = Query(qi[None], qv[None])
        ref = union.search_typed(Query(qi[None], qv[None]))
        _warm_replicas(sess, q)
        sess.search_typed(q)                # seed the window, warm
        # make shard 0's primary a straggler, far past the 20ms threshold
        sess.router._sessions[0][0] = _Slow(sess.router._sessions[0][0], 0.6)
        t0 = time.monotonic()
        res = sess.search_typed(q)
        wall = time.monotonic() - t0
        np.testing.assert_array_equal(res.doc_ids, ref.doc_ids)
        np.testing.assert_array_equal(res.scores, ref.scores)
        st = sess.last_stats
        assert st.hedges >= 1 and st.hedge_wins >= 1
        assert not st.partial and st.shards_missing == ()
        # slow is not failed: the straggler stays in rotation
        assert not sess.router._down[0][0]
        assert wall < 0.55, f"hedge did not outrun the 0.6s straggler " \
                            f"({wall*1e3:.0f}ms)"
    finally:
        sess.close()
        union.close()


def test_hedge_per_query_opt_out_pins_it_off(tmp_path):
    cfg = smoke()
    corpus, sess, union = _cluster(
        tmp_path, cfg,
        hedge_policy=HedgePolicy(percentile=0.5, min_ms=1.0, fallback_ms=5.0))
    try:
        qi, qv = corpus_lib.make_query(corpus, 3, cfg.max_query_nnz)
        q = Query(qi[None], qv[None])
        sess.search_typed(q)
        sess.router._sessions[0][0] = _Slow(sess.router._sessions[0][0], 0.15)
        res = sess.search_typed(q, options=QueryOptions(hedging=False))
        assert sess.last_stats.hedges == 0  # opt-out beat the router default
        ref = union.search_typed(Query(qi[None], qv[None]))
        np.testing.assert_array_equal(res.doc_ids, ref.doc_ids)
    finally:
        sess.close()
        union.close()


def test_hedge_per_query_opt_in_without_router_policy(tmp_path):
    """hedging=True arms the default policy even when the router was
    built without one; counters land in the shared registry."""
    cfg = smoke()
    obs = Obs(registry=MetricsRegistry())
    corpus, sess, union = _cluster(tmp_path, cfg, obs=obs)
    try:
        assert sess.router.hedge_policy is None
        qi, qv = corpus_lib.make_query(corpus, 5, cfg.max_query_nnz)
        q = Query(qi[None], qv[None])
        sess.search_typed(q)
        _warm_replicas(sess, q)
        sess.router._sessions[1][0] = _Slow(sess.router._sessions[1][0], 0.5)
        # default fallback is 50ms; the 0.5s straggler trips it
        res = sess.search_typed(q, options=QueryOptions(hedging=True))
        st = sess.last_stats
        assert st.hedges >= 1 and st.hedge_wins >= 1
        ref = union.search_typed(Query(qi[None], qv[None]))
        np.testing.assert_array_equal(res.doc_ids, ref.doc_ids)
        reg = obs.registry
        assert reg.counter("cluster_hedges_total").value >= 1
        assert reg.counter("cluster_hedge_wins_total").value >= 1
    finally:
        sess.close()
        union.close()
