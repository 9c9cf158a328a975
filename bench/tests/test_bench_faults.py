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
from repro.core.engine import PatternSearchEngine, SearchResult
from repro.storage.plan import Planner
from repro.storage.session import FlashSearchSession

DOCS = 9000           # three 4096-doc segments (the last one partial)
# what each run drives: a cell of BENCHMARK.json, or a cell's configuration
# under a mix kept for a later cell (the 32-caller closed loop, which fills
# every coalesced batch)
RUNS = {"pubmed-shard32.open": ("pubmed-shard32.open", None),
        "pubmed-shard32.batch": ("pubmed-shard32.open", "mlt-closed-32")}


def small(name: str) -> spec.Cell:
    workload, mix = RUNS[name]
    cell = spec.resolve(workload)
    cell.config = dict(cell.config, n_docs=DOCS)
    cell.traffic = dict(spec.traffic(mix) if mix else cell.traffic,
                        check_sample=12)
    if "rate_qps" in cell.traffic:
        cell.traffic["rate_qps"] = 8.0
    else:                   # a closed loop's user pays for throughput
        cell.end_to_end = cell.end_to_end + [
            {"name": "queries_per_s", "unit": "queries/s"}]
    return cell


def run(workload, tmp_path, seed=2**31 + 11):
    return harness.run_cell(small(workload), seed, 1.5, False,
                            t_start=time.perf_counter(), root=str(tmp_path),
                            require_tpu=False, compile_cache=False)


@pytest.mark.parametrize("workload", ["pubmed-shard32.open",
                                      "pubmed-shard32.batch"])
def test_sound_run_is_correct(workload, tmp_path):
    out = run(workload, tmp_path)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert {m["name"] for m in small(workload).end_to_end} == set(
        out["metrics"])


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


@pytest.mark.parametrize("fault,workload", [
    (_altered_scores, "pubmed-shard32.open"),
    (_altered_ids, "pubmed-shard32.open"),
    (_half_the_slabs, "pubmed-shard32.open"),
    (_half_the_batch, "pubmed-shard32.batch"),
])
def test_broken_timed_path_is_not_correct(fault, workload, tmp_path,
                                          monkeypatch):
    fault(monkeypatch)
    out = run(workload, tmp_path)
    assert not out["correct"], out["checks"]
