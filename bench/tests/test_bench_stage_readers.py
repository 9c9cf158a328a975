"""The readers of the program's stage histograms: the window's sum per
flushed batch, and nothing where the stage never ran."""
import pytest

from bench import spec

READERS = [("plan_ms", "plan"), ("slab_prep_ms", "slab_prep"),
           ("slab_dispatch_ms", "slab_dispatch"),
           ("slab_wait_ms", "slab_wait"), ("load_decode_ms", "decode")]


@pytest.mark.parametrize("metric,label", READERS)
def test_reader_gives_sum_per_batch(metric, label):
    rec = {"batches": 4, "delta": {
        f"stage_ms{{stage={label}}}": (252, 1000.0),
        "stage_ms{stage=score}": (252, 9999.0)}}
    assert spec.part("metrics", metric + ".p50").read(rec) == 250.0


@pytest.mark.parametrize("metric,label", READERS)
def test_reader_finds_nothing_without_observations(metric, label):
    reader = spec.part("metrics", metric + ".p50")
    assert reader.read({"batches": 4, "delta": {
        f"stage_ms{{stage={label}}}": (0, 0.0)}}) is None
    assert reader.read({"batches": 4, "delta": {}}) is None
    assert reader.read({"batches": 0, "delta": {
        f"stage_ms{{stage={label}}}": (3, 1.0)}}) is None
