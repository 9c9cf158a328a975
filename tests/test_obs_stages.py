"""The program's own stage spans (DESIGN.md §8.2): every ``repro.*``
stage lands on the profiler's timeline on the thread that ran it, a
slab's dispatch nests inside its score stage and that inside the
service's batch, a pass's prepare and collect run once inside the batch
and outside every score stage, and the engine's ``stage_ms`` histograms
count them without changing an answer."""
import glob
import os
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.paper_search import smoke
from repro.core import corpus as corpus_lib
from repro.obs import Obs
from repro.serve.api import Query
from repro.storage import FlashSearchSession, FlashStore

CFG = smoke()
SEGMENTS = 8
STAGES = ("batch", "plan", "decode", "upload", "prefetch_wait", "score",
          "slab_prep", "slab_dispatch", "slab_wait", "merge")
SLAB_STAGES = ("slab_prep", "slab_dispatch", "slab_wait")


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    corpus = corpus_lib.synthesize(400, CFG.vocab_size, CFG.avg_nnz_per_doc,
                                   CFG.nnz_pad, seed=11)
    root = str(tmp_path_factory.mktemp("stages") / "store")
    FlashStore.create(root, vocab_size=CFG.vocab_size,
                      docs_per_segment=400 // SEGMENTS).append_corpus(corpus)
    # one slab's device bytes, so a cache can be sized to hold part of
    # the store: later queries then mix cache hits with loads
    probe = FlashSearchSession(FlashStore.open(root), CFG,
                               obs=Obs.disabled(), use_filter=False)
    probe.search_typed(_query(corpus, 0))
    slab_bytes = probe.slab_cache.nbytes // len(probe.slab_cache)
    probe.close()
    return corpus, root, slab_bytes


def _query(corpus, doc):
    return Query(*corpus_lib.make_query(corpus, doc, CFG.max_query_nnz))


def _session(store, obs):
    corpus, root, slab_bytes = store
    return FlashSearchSession(FlashStore.open(root), CFG, obs=obs,
                              use_filter=False,
                              cache_bytes=3 * slab_bytes + slab_bytes // 2)


def _repro_events(log_dir):
    """[(thread line name, stage, start_ns, end_ns)] of every ``repro.*``
    event on the host plane."""
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append((line.name, e.name[len("repro."):],
                                e.start_ns, e.start_ns + e.duration_ns))
    return out


def _inside(ev, outer):
    return any(o[0] == ev[0] and o[2] <= ev[2] and ev[3] <= o[3]
               for o in outer)


def test_stage_spans_on_the_profiler_timeline(store, tmp_path):
    corpus = store[0]
    sess = _session(store, Obs())
    # a slower loader, so the scan has to block on the prefetcher
    put_slab = sess.engine.put_slab

    def slow_put(slab):
        time.sleep(0.01)
        return put_slab(slab)

    sess.engine.put_slab = slow_put
    svc = sess.service(max_batch=4)
    with jax.profiler.trace(str(tmp_path / "trace")):
        for docs in ((3, 50), (120, 260, 333), (7,)):
            for f in [svc.submit(_query(corpus, d)) for d in docs]:
                f.result()
    sess.close()
    events = _repro_events(str(tmp_path / "trace"))
    by = {s: [e for e in events if e[1] == s] for s in STAGES}
    assert all(by[s] for s in STAGES), {s: len(by[s]) for s in STAGES}
    # the loader's stages run on the prefetch thread, the rest on the
    # service's scheduler thread
    assert {e[0] for s in ("decode", "upload") for e in by[s]} == {
        "slab-prefetch"}
    for s in STAGES:
        if s not in ("decode", "upload"):
            assert {e[0] for e in by[s]} == {"search-service"}, s
    # a pass prepares its query before the scan and collects every slab
    # after it: only the dispatch runs inside a slab's score stage
    assert all(_inside(e, by["score"]) for e in by["slab_dispatch"])
    for s in ("slab_prep", "slab_wait"):
        assert not any(_inside(e, by["score"]) for e in by[s]), s
    for s in ("plan", "score", "prefetch_wait", "merge", "slab_prep",
              "slab_wait"):
        assert all(_inside(e, by["batch"]) for e in by[s]), s


def test_slab_stages_split_the_score_stage(store):
    corpus = store[0]
    queries = [_query(corpus, d) for d in (3, 120, 260, 333, 7)]
    obs = Obs()
    on = _session(store, obs)
    t0 = time.perf_counter()
    got = [on.search_typed(q) for q in queries]
    passes_ms = (time.perf_counter() - t0) * 1e3
    on.close()
    off = _session(store, Obs.disabled())
    want = [off.search_typed(q) for q in queries]
    off.close()
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a.doc_ids, b.doc_ids)
        np.testing.assert_array_equal(a.scores, b.scores)
    st = {s: obs.registry.histogram("stage_ms", stage=s).state()
          for s in ("score", "decode") + SLAB_STAGES}
    assert st["score"].total == len(queries) * SEGMENTS
    assert st["decode"].total > 0     # the cache held part of the store
    # one dispatch a slab, inside its score stage; one prepare and one
    # collect a pass
    assert st["slab_dispatch"].total == st["score"].total
    assert st["slab_dispatch"].sum < st["score"].sum
    for s in ("slab_prep", "slab_wait"):
        assert st[s].total == len(queries), s
    assert sum(st[s].sum for s in SLAB_STAGES) < passes_ms
