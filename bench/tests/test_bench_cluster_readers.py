"""The readers of the cluster router's stages, on hand-made records, and
one traced run of ``pubmed-4shard.open`` at a small size on the CPU's one
device, which has to read ``correct`` with all three present."""
import time

import pytest

from bench import harness, spec

CELL = "pubmed-4shard.open"
NEW = ("shard_ms.p50", "gather_ms.p50", "shard_skew_ms.p50")


def rec(**delta):
    return {"batches": 4, "delta": delta}


def test_shard_ms_is_the_mean_shard_search():
    r = rec(**{"stage_ms{stage=shard}": (16, 800.0),
               "stage_ms{stage=gather}": (4, 9999.0)})
    assert spec.part("metrics", "shard_ms.p50").read(r) == 50.0


def test_gather_ms_is_the_sum_per_batch():
    r = rec(**{"stage_ms{stage=gather}": (5, 1000.0),
               "stage_ms{stage=shard}": (20, 9999.0)})
    assert spec.part("metrics", "gather_ms.p50").read(r) == 250.0


def test_shard_skew_is_the_straggler_beyond_a_mean_shard():
    r = rec(**{"cluster_straggler_ms{}": (4, 320.0),
               "stage_ms{stage=shard}": (16, 800.0)})
    assert spec.part("metrics", "shard_skew_ms.p50").read(r) == 30.0


@pytest.mark.parametrize("metric", NEW)
def test_reader_finds_nothing_without_router_stages(metric):
    """What a program without the router's stages records: the readers
    leave their metric out, and raise nothing."""
    reader = spec.part("metrics", metric)
    assert reader.read(rec()) is None
    assert reader.read(rec(**{"cluster_straggler_ms{}": (4, 320.0)})) is None
    assert reader.read(rec(**{"stage_ms{stage=shard}": (0, 0.0),
                              "stage_ms{stage=gather}": (0, 0.0)})) is None


def test_cell_names_the_new_metrics():
    cell = spec.resolve(CELL)
    assert cell.chips == 4 and cell.config["surface"] == "cluster"
    assert set(NEW) <= {m["name"] for m in cell.per_layer}
    assert {m["name"] for m in cell.end_to_end} == {"query_p50_ms",
                                                   "setup_s"}


def test_small_traced_run_is_correct(tmp_path):
    """The cell at 9,000 documents (4 shards x 2 replicas, one segment a
    shard, all on the CPU's one device), traced: correct, and the three
    router metrics read."""
    cell = spec.resolve(CELL)
    cell.chips = 1
    cell.config = dict(cell.config, n_docs=9000)
    cell.traffic = dict(cell.traffic, check_sample=12, rate_qps=8.0)
    out = harness.run_cell(cell, 2**31 + 29, 1.5, True,
                           t_start=time.perf_counter(), root=str(tmp_path),
                           require_tpu=False, compile_cache=False)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    for name in NEW:
        assert name in out["metrics"], sorted(out["metrics"])
    assert out["metrics"]["shard_ms.p50"]["value"] > 0
    assert out["metrics"]["gather_ms.p50"]["value"] > 0
    # the slowest shard's wall time is read inside its stage, a few
    # microseconds short of the stage's own
    assert out["metrics"]["shard_skew_ms.p50"]["value"] > -0.05
