"""A whole run at a small size on the CPU (the look for a chip skipped),
sound and with the timed path broken underneath: ``correct`` has to come
out true for the sound run and false for each fault this kind of cell can
have. The faults are planted in the program's classes, under the
benchmark, for the one run."""
import dataclasses
import time

import numpy as np
import pytest

from bench import harness, spec
from repro.cluster.router import ShardRouter
from repro.core.engine import PatternSearchEngine, SearchResult
from repro.storage.plan import Planner
from repro.storage.session import FlashSearchSession

DOCS = 9000           # three 4096-doc segments (the last one partial)
# the cluster surface at the same size: 4 shards x 2 replicas, all on the
# CPU's one device, one segment a shard
CLUSTER = {"surface": "cluster", "n_shards": 4, "replicas": 2,
           "policy": "range"}
# what each run drives: a cell of BENCHMARK.json, and the changes made to
# its configuration
RUNS = {"pubmed-shard32.open": ("pubmed-shard32.open", {}),
        "pubmed-shard32.batch": ("pubmed-shard32.batch", {}),
        "cluster-4x2.open": ("pubmed-shard32.open", CLUSTER)}


def small(name: str) -> spec.Cell:
    workload, changes = RUNS[name]
    cell = spec.resolve(workload)
    cell.config = dict(cell.config, n_docs=DOCS, **changes)
    cell.traffic = dict(cell.traffic, check_sample=12)
    if "rate_qps" in cell.traffic:
        cell.traffic["rate_qps"] = 8.0
    return cell


def run(workload, tmp_path, seed=2**31 + 11, trace=False):
    return harness.run_cell(small(workload), seed, 1.5, trace,
                            t_start=time.perf_counter(), root=str(tmp_path),
                            require_tpu=False, compile_cache=False)


@pytest.mark.parametrize("workload", sorted(RUNS))
def test_sound_run_is_correct(workload, tmp_path):
    out = run(workload, tmp_path)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert {m["name"] for m in small(workload).end_to_end} == set(
        out["metrics"])


def test_traced_cluster_run_is_correct(tmp_path):
    """A traced run of the cluster surface reaches its check: the trace
    reduction needs nothing of one store's session."""
    out = run("cluster-4x2.open", tmp_path, trace=True)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) <= {
        m["name"] for m in small("cluster-4x2.open").per_layer}
    assert out["metrics"]["batch_occupancy.p50"]["value"] > 0


def _altered_scores(monkeypatch):
    real = PatternSearchEngine._search_arrays

    def search(self, q_ids, q_vals):
        r = real(self, q_ids, q_vals)
        return SearchResult(r.doc_ids, r.scores + np.float32(1e-3))
    monkeypatch.setattr(PatternSearchEngine, "_search_arrays", search)


def _altered_ids(monkeypatch):
    real = PatternSearchEngine._search_arrays

    def search(self, q_ids, q_vals):
        r = real(self, q_ids, q_vals)
        ids = r.doc_ids.copy()
        ids[:, 1:] = np.where(ids[:, 1:] >= 0, ids[:, 1:] + 1, ids[:, 1:])
        return SearchResult(ids, r.scores)
    monkeypatch.setattr(PatternSearchEngine, "_search_arrays", search)


def _half_the_slabs(monkeypatch):
    real = Planner.plan

    def plan(self, *a, **kw):
        p = real(self, *a, **kw)
        return dataclasses.replace(p, steps=p.steps[::2])
    monkeypatch.setattr(Planner, "plan", plan)


def _half_the_batch(monkeypatch):
    real = FlashSearchSession.search_typed

    def search_typed(self, query, options=None, **kw):
        r = real(self, query, options, **kw)
        ids, sc = r.doc_ids.copy(), r.scores.copy()
        half = ids.shape[0] // 2
        if half:
            ids[half:], sc[half:] = ids[0], sc[0]
        return SearchResult(ids, sc)
    monkeypatch.setattr(FlashSearchSession, "search_typed", search_typed)


def _one_shard_dropped(monkeypatch):
    real = ShardRouter._search_shard

    def search_shard(self, shard, query, *a, **kw):
        res, *rest = real(self, shard, query, *a, **kw)
        if shard == 1:
            res = SearchResult(np.full_like(res.doc_ids, -1),
                               np.full_like(res.scores, -np.inf))
        return (res, *rest)
    monkeypatch.setattr(ShardRouter, "_search_shard", search_shard)


@pytest.mark.parametrize("fault,workload", [
    (_altered_scores, "pubmed-shard32.open"),
    (_altered_ids, "pubmed-shard32.open"),
    (_half_the_slabs, "pubmed-shard32.open"),
    (_half_the_batch, "pubmed-shard32.batch"),
    (_one_shard_dropped, "cluster-4x2.open"),
])
def test_broken_timed_path_is_not_correct(fault, workload, tmp_path,
                                          monkeypatch):
    fault(monkeypatch)
    out = run(workload, tmp_path)
    assert not out["correct"], out["checks"]
