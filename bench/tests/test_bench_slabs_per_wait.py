"""The reader of slabs dispatched per blocking wait: the ratio of the
window's stage counts, and nothing where no wait was observed."""
from bench import spec

READER = spec.part("metrics", "slabs_per_wait.p50")


def test_ratio_of_dispatch_to_wait_counts():
    rec = {"batches": 4, "delta": {
        "stage_ms{stage=slab_dispatch}": (252, 80.0),
        "stage_ms{stage=slab_wait}": (4, 300.0),
        "stage_ms{stage=score}": (252, 900.0)}}
    assert READER.read(rec) == 63.0


def test_one_wait_a_slab_reads_one():
    rec = {"batches": 4, "delta": {
        "stage_ms{stage=slab_dispatch}": (252, 80.0),
        "stage_ms{stage=slab_wait}": (252, 700.0)}}
    assert READER.read(rec) == 1.0


def test_nothing_without_a_wait():
    assert READER.read({"batches": 4, "delta": {}}) is None
    assert READER.read({"batches": 4, "delta": {
        "stage_ms{stage=slab_dispatch}": (5, 1.0),
        "stage_ms{stage=slab_wait}": (0, 0.0)}}) is None
