"""Distributed engine numerics on a real (placeholder) multi-device mesh.

Runs in a subprocess so the 8-device XLA_FLAGS never leaks into the other
tests (they must see 1 device per the assignment)."""
import json
import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import dataclasses
import numpy as np
import jax
from jax.sharding import Mesh
from repro.configs.paper_search import smoke
from repro.core import corpus as corpus_lib
from repro.core.engine import PatternSearchEngine
from repro.distributed.meshctx import MeshCtx

assert len(jax.devices()) == 8
mesh = jax.make_mesh((4, 2), ("data", "model"))
ctx = MeshCtx(mesh=mesh, dp_axes=("data",), fsdp_axis="data",
              tp_axis="model")
cfg = smoke()
corpus = corpus_lib.synthesize(256, cfg.vocab_size, cfg.avg_nnz_per_doc,
                               cfg.nnz_pad, seed=5)
eng = PatternSearchEngine(corpus, cfg, ctx, backend="jnp")
idxs = [3, 77, 150, 200]   # L=4 over model axis of 2
qs = [corpus_lib.make_query(corpus, i, cfg.max_query_nnz) for i in idxs]
qi = np.stack([q[0] for q in qs]); qv = np.stack([q[1] for q in qs])
r = eng.search(qi, qv)
print(json.dumps({
    "top1": [int(x) for x in r.doc_ids[:, 0]],
    "score1": [float(x) for x in r.scores[:, 0]],
}))
"""


def test_engine_on_8_device_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["top1"] == [3, 77, 150, 200]          # self-search exact
    for s in res["score1"]:
        assert abs(s - 1.0) < 1e-4


PLACEMENT_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys, tempfile
import numpy as np
import jax
from repro.cluster import FlashClusterSession, build_sharded_store
from repro.configs.paper_search import smoke
from repro.core import corpus as corpus_lib
from repro.obs import NULL_SPAN
from repro.serve import Query
from repro.storage import FlashSearchSession, FlashStore
from repro.storage.store import _corpus_docs

assert len(jax.devices()) == 4
cfg = smoke()
corpus = corpus_lib.synthesize(400, cfg.vocab_size, cfg.avg_nnz_per_doc,
                               cfg.nnz_pad, seed=9)
docs = _corpus_docs(corpus)
qs = [corpus_lib.make_query(corpus, i, cfg.max_query_nnz)
      for i in (3, 150, 333)]
q = Query(np.stack([x[0] for x in qs]), np.stack([x[1] for x in qs]))
out = {}
root = tempfile.mkdtemp(dir=sys.argv[1])
for backend in ("jnp", "pallas_fused"):
    union = FlashStore.create(os.path.join(root, backend + "-union"),
                              vocab_size=cfg.vocab_size, docs_per_segment=64)
    union.append_docs(docs)
    with FlashSearchSession(union, cfg, backend=backend) as one:
        want = one.search_typed(q)
    cl = build_sharded_store(os.path.join(root, backend), docs, n_shards=4,
                             replicas=2, policy="range",
                             vocab_size=cfg.vocab_size, docs_per_segment=64)
    with FlashClusterSession(cl, cfg, backend=backend) as sess:
        router = sess.router
        got = sess.search_typed(q)
        for s in range(4):                 # query every replica once
            for r in range(2):
                router._attempt(s, r, q, NULL_SPAN)
        placed, arrays_ok = {}, True
        owner = {}
        for s in range(4):
            for r in range(2):
                sess_sr = router._sessions[s][r]
                dev = router.device_of(s, r)
                placed[f"{s},{r}"] = dev.id
                mesh_devs = set(sess_sr.engine.ctx.mesh.devices.flat)
                arrays_ok &= mesh_devs == {dev}
                owner[sess_sr.store.cache_token] = dev
        n_slabs = 0
        for dev, cache in router.slab_caches.items():
            for key in cache.keys():
                arrays_ok &= owner[key[0]] == dev
                for a in cache.get(key).slab:
                    n_slabs += 1
                    arrays_ok &= a.devices() == {dev}
    out[backend] = {
        "same": bool(np.array_equal(got.doc_ids, want.doc_ids)
                     and np.array_equal(got.scores, want.scores)),
        "placed": placed, "arrays_ok": bool(arrays_ok),
        "n_arrays": n_slabs}
print(json.dumps(out))
"""


def test_shard_replicas_each_own_a_device(tmp_path):
    """4 shards x 2 replicas over 4 (virtual) devices: replica r of shard
    s lives on device (s + r) % 4 — every session's mesh and every slab
    it uploaded sit on that device — and the answers still equal a
    single-store scan of the union corpus, on both backends."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", PLACEMENT_SCRIPT,
                          str(tmp_path)], env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    for backend, r in res.items():
        assert r["same"], backend
        assert r["arrays_ok"] and r["n_arrays"] > 0, (backend, r)
        for s in range(4):
            devs = {r["placed"][f"{s},{rep}"] for rep in range(2)}
            assert devs == {s % 4, (s + 1) % 4}, (backend, r["placed"])
        primaries = {r["placed"][f"{s},0"] for s in range(4)}
        assert primaries == {0, 1, 2, 3}
