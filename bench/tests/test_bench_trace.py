"""The trace reduction, on a short trace recorded on a TPU v5e from the
shard32 cell (three seconds or less of the served path)."""
import glob
import os

import numpy as np
import pytest

from bench import spec, trace_reduce
from bench.peaks import peaks_for

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def reduced():
    path = sorted(glob.glob(os.path.join(DATA, "*.xplane.pb")))[0]
    return trace_reduce.reduce_trace(path)


def test_busy_and_window(reduced):
    assert reduced["chips"] == 1
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_breakdown_lists(reduced):
    ops, gaps = reduced["device_ops"], reduced["idle_gaps"]
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert all(isinstance(n, str) and s > 0 for n, s in ops)
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(s for _, s in gaps) <= idle + 1e-6
    assert any("score" in n for n, _ in gaps)


def test_scoring_program_and_its_roofline(reduced):
    prog = reduced["programs"]["jit_search"]
    assert prog["runs"] > 0 and prog["device_s"] > 0
    rec = {"trace": reduced, "peaks": peaks_for("TPU v5 lite"),
           "segment_stream_bytes": 976000.0}
    share = spec.part("metrics", "scan_roofline").read(rec)
    assert 0 < share < 100
    idle = spec.part("metrics", "device_idle_share").read(rec)
    assert 0 < idle < 100


def test_readers_find_nothing_without_a_trace():
    rec = {"trace": {}, "peaks": None, "segment_stream_bytes": 1.0}
    assert spec.part("metrics", "scan_roofline").read(rec) is None
    assert spec.part("metrics", "device_idle_share").read(rec) is None


def test_union_and_gap_labels():
    u = trace_reduce._union(np.array([[0, 2], [1, 3], [5, 6], [5.5, 5.7]]))
    assert u.tolist() == [[0, 3], [5, 6]]
    spans = {"bench.batch": np.array([[0.0, 10.0]]),
             "bench.score": np.array([[3.5, 4.5], [8.0, 9.0]])}
    labels = trace_reduce._covering(spans, np.array([4.0, 6.0, 11.0]))
    assert labels == ["batch+score", "batch", "none"]


def test_op_names_cut_to_name_and_shape():
    text = ("%fusion.1 = f32[524288,8]{1,0:T(8,128)} fusion(f32[141043,8]"
            "{1,0} %fusion.3), kind=kCustom")
    assert trace_reduce.op_name(text) == "%fusion.1 = f32[524288,8]"


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        peaks_for("TPU v99")
