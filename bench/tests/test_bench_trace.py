"""The trace reduction, on a short trace recorded on a TPU v5e: one second
of the served path of the shard32 configuration cut to 65,536 docs
(``bench/sweep.py --keep-trace``), with the program's own stage spans."""
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
    if len(gaps) < 10:      # every label listed: the split covers the idle
        assert sum(s for _, s in gaps) == pytest.approx(idle)
    assert all(part == "none" or part.startswith("repro.")
               for n, _ in gaps for part in n.split("+"))
    assert any(n == "repro.plan" for n, _ in gaps)


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
    s = 1e9                 # trace times are in ns; the split gives seconds
    threads = {
        "search-service": {"repro.batch": [(0, 10 * s)],
                           "repro.score": [(3.5 * s, 4.5 * s),
                                           (8 * s, 9 * s)]},
        "slab-prefetch": {"repro.decode": [(5 * s, 7 * s)]}}
    gaps = np.array([[3, 6], [10.5, 11]]) * s
    split = dict(trace_reduce.split_idle(gaps, threads, top=10))
    assert split == pytest.approx({
        "repro.batch": 1.0, "repro.score": 1.0,
        "repro.batch+repro.decode": 1.0, "none": 0.5})
    assert trace_reduce.split_idle(gaps, {}, top=10) == [["none", 3.5]]


def test_op_names_cut_to_name_and_shape():
    text = ("%fusion.1 = f32[524288,8]{1,0:T(8,128)} fusion(f32[141043,8]"
            "{1,0} %fusion.3), kind=kCustom")
    assert trace_reduce.op_name(text) == "%fusion.1 = f32[524288,8]"


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        peaks_for("TPU v99")
