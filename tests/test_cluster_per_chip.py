"""The cluster on several chips (DESIGN.md §4.2, §8.2): one slab cache a
chip, shared by the shard-replica sessions placed there, and the router's
own ``repro.shard`` / ``repro.gather`` stages."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.cluster import FlashClusterSession
from repro.cluster.store import build_sharded_store
from repro.configs.paper_search import smoke
from repro.core import corpus as corpus_lib
from repro.obs import Obs
from repro.obs import trace as obs_trace
from repro.serve import Query
from repro.storage.slabcache import SlabCache
from repro.storage.store import _corpus_docs

CFG = smoke()
ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

PER_CHIP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys, tempfile
import numpy as np
import jax
from bench.references import cosine
from repro.cluster import FlashClusterSession, build_sharded_store
from repro.configs.paper_search import smoke
from repro.core import corpus as corpus_lib
from repro.serve import Query
from repro.storage.store import _corpus_docs

assert len(jax.devices()) == 4
cfg = smoke()
corpus = corpus_lib.synthesize(640, cfg.vocab_size, cfg.avg_nnz_per_doc,
                               cfg.nnz_pad, seed=17)
cl = build_sharded_store(tempfile.mkdtemp(dir=sys.argv[1]),
                         _corpus_docs(corpus), n_shards=4, replicas=2,
                         policy="range", vocab_size=cfg.vocab_size,
                         docs_per_segment=64)
docs = list(range(3, 640, 29))
qs = [corpus_lib.make_query(corpus, d, cfg.max_query_nnz) for d in docs]
out = {}
with FlashClusterSession(cl, cfg) as sess:
    router = sess.router

    def one_pass():
        futs = [sess.submit(Query(*q)) for q in qs]
        rows = [f.result(timeout=300) for f in futs]
        return (np.stack([np.ravel(r.doc_ids) for r in rows]),
                np.stack([np.ravel(r.scores) for r in rows]))

    s0 = sess.cache_stats
    ids, scores = one_pass()
    s1 = sess.cache_stats
    ids2, scores2 = one_pass()
    s2 = sess.cache_stats
    out["caches"] = len(sess.slab_caches)
    out["distinct"] = len({id(c) for c in sess.slab_caches.values()})
    own, only_own = [], []
    for s in range(4):
        primary = router._sessions[s][0]
        dev = router.device_of(s, 0)
        cache = sess.slab_caches[dev]
        own.append(primary.slab_cache is cache)
        tokens = {k[0] for k in cache.keys()}
        only_own.append(tokens == {primary.store.cache_token})
    out["own"], out["only_own"] = own, only_own
    out["first"] = [s1.hits - s0.hits, s1.misses - s0.misses]
    out["second"] = [s2.hits - s1.hits, s2.misses - s1.misses]
    out["repeat_same"] = bool(np.array_equal(ids, ids2)
                              and np.array_equal(scores, scores2))
ref = cosine.Reference(corpus.ids, corpus.vals, cfg.vocab_size)
cos = ref.cos(np.stack([q[0] for q in qs]), np.stack([q[1] for q in qs]))
out["judged"] = cosine.judge(ids, scores, cos, cfg.top_k, docs)
print(json.dumps(out))
"""


def test_each_chip_holds_its_own_shard_in_its_own_cache(tmp_path):
    """4 shards x 2 replicas (range) over 4 (virtual) devices, queried
    through ``submit``: each primary draws on its device's cache, each
    cache holds only that shard's slabs, a second pass is all hits, and
    the answers equal the float64 cosine over the union corpus."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(ROOT, "src"), ROOT])
    out = subprocess.run([sys.executable, "-c", PER_CHIP_SCRIPT,
                          str(tmp_path)], env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["caches"] == res["distinct"] == 4
    assert all(res["own"]) and all(res["only_own"]), res
    hits, misses = res["first"]
    assert misses > 0
    hits, misses = res["second"]
    assert hits > 0 and misses == 0, res
    assert res["repeat_same"]
    from bench.references import cosine
    for key, limit in cosine.LIMITS.items():
        assert res["judged"][key] <= limit, res["judged"]


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    corpus = corpus_lib.synthesize(400, CFG.vocab_size, CFG.avg_nnz_per_doc,
                                   CFG.nnz_pad, seed=19)
    root = str(tmp_path_factory.mktemp("cluster") / "c")
    build_sharded_store(root, _corpus_docs(corpus), n_shards=4, replicas=2,
                        policy="range", vocab_size=CFG.vocab_size,
                        docs_per_segment=50)
    return corpus, root


def _query(corpus, docs):
    qs = [corpus_lib.make_query(corpus, d, CFG.max_query_nnz) for d in docs]
    return Query(np.stack([q[0] for q in qs]), np.stack([q[1] for q in qs]))


def test_router_stages_count_shards_and_gathers(cluster):
    corpus, root = cluster
    obs = Obs()
    with FlashClusterSession(root, CFG, obs=obs) as sess:
        for docs in ([1], [5, 77], [120, 240, 399]):
            sess.search(_query(corpus, docs))
        futs = [sess.submit(Query(*corpus_lib.make_query(
            corpus, d, CFG.max_query_nnz))) for d in (9, 19, 29)]
        for f in futs:
            f.result(timeout=60)
        searches = 3 + sess.service().stats.n_batches
    reg = obs.registry
    assert reg.histogram("stage_ms", stage="shard").count == 4 * searches
    assert reg.histogram("stage_ms", stage="gather").count == searches
    # each shard's stage lies inside its batch's gather
    shard = reg.histogram("stage_ms", stage="shard")
    gather = reg.histogram("stage_ms", stage="gather")
    assert 0 < shard.sum <= 4 * gather.sum
    assert reg.histogram("cluster_straggler_ms").count == searches


def test_router_stages_annotate_only_when_observed(cluster, monkeypatch):
    corpus, root = cluster
    opened = []

    class Recorder:
        def __init__(self, name, **kw):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(obs_trace, "TraceAnnotation", Recorder)
    q = _query(corpus, [3, 30])
    with FlashClusterSession(root, CFG, obs=Obs.disabled()) as sess:
        off = sess.search(q)
    assert opened == []
    with FlashClusterSession(root, CFG, obs=Obs()) as sess:
        on = sess.search(q)
    assert opened.count("repro.shard") == 4
    assert opened.count("repro.gather") == 1
    np.testing.assert_array_equal(off.doc_ids, on.doc_ids)
    np.testing.assert_array_equal(off.scores, on.scores)


def test_cluster_trace_nests_replicas_under_shard_stages(cluster):
    corpus, root = cluster
    obs = Obs(trace_sample=1)
    with FlashClusterSession(root, CFG, obs=obs) as sess:
        sess.search(_query(corpus, [42]))
        tr = sess.last_trace
    assert tr is not None and tr.well_formed()
    shards = [c for c in tr.root.children if c.name == "shard"]
    assert sorted(c.attrs["shard"] for c in shards) == [0, 1, 2, 3]
    for sh in shards:
        assert sh.attrs["replica"] == 0 and sh.attrs["wall_ms"] >= 0
        assert [c.name for c in sh.children] == ["replica"]
    (gather,) = [c for c in tr.root.children if c.name == "gather"]
    assert gather.attrs["shards_merged"] == 4
    # the gather runs from the scatter's submission to the merged answer
    assert all(gather.t0 <= sh.t0 and sh.t1 <= gather.t1 for sh in shards)


def test_cache_per_device_sizes_and_switch_off(cluster):
    corpus, root = cluster
    with FlashClusterSession(root, CFG, cache_bytes=1 << 20) as sess:
        sess.search(_query(corpus, [8]))
        caches = sess.slab_caches
        assert len(caches) == 1            # the one CPU device
        (cache,) = caches.values()
        assert cache.max_bytes == 1 << 20
        assert sess.cache_stats.misses == cache.stats_snapshot().misses > 0
    with FlashClusterSession(root, CFG, cache_bytes=0) as sess:
        sess.search(_query(corpus, [8]))
        assert sess.slab_caches == {} and sess.cache_stats is None
        assert all(s.slab_cache is None
                   for s in sess.router._open_sessions())


def test_publish_cache_labels_each_chip():
    obs = Obs()
    one, two = SlabCache(1 << 20), SlabCache(2 << 20)

    class Dev:
        def __init__(self, i):
            self.id = i

    obs.publish_cache({Dev(0): one, Dev(3): two})
    names = {(name, tuple(sorted(labels.items())))
             for name, labels, kind, _ in obs.registry.items()
             if kind == "gauge"}
    for dev in ("0", "3"):
        assert ("slab_cache_bytes", (("device", dev),)) in names
        assert ("slab_cache_hits_lifetime", (("device", dev),)) in names
    assert not any(labels == () for _, labels in names)
    # a single store's cache keeps its unlabelled series
    obs.publish_cache(one)
    assert obs.registry.gauge("slab_cache_entries").value == 0
    assert ("slab_cache_entries", ()) in {
        (name, tuple(sorted(labels.items())))
        for name, labels, kind, _ in obs.registry.items()}
