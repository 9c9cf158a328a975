"""Every cell of BENCHMARK.json resolves to its files by name, and the file
keeps to the benchmark's contract."""
import json
import os
import re

import pytest

from bench import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_workload_resolves_by_name(workload):
    cell = spec.resolve(workload)
    arrivals = spec.part("arrivals", cell.traffic["arrivals"])
    assert callable(arrivals.prepare) and callable(arrivals.drive)
    assert callable(spec.part("selectors", cell.traffic["selector"]).pick)
    gen = spec.part("generators", cell.config["generator"])
    assert callable(gen.generate) and callable(
        getattr(gen, cell.traffic["queries"]))
    assert spec.part("references", cell.config["reference"]).Reference
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    for name in names:
        assert callable(spec.part("e2e", name).read)
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(spec.part("metrics", m["name"]).read)
        assert m["moves"] in names


def test_every_mix_file_resolves_by_name():
    """Mixes kept for later cells resolve too, so adding their cell takes
    only an entry in BENCHMARK.json."""
    mixes = sorted(f[:-5] for f in os.listdir(os.path.join(spec.BENCH_DIR,
                                                            "traffic"))
                   if f.endswith(".json"))
    assert mixes
    for name in mixes:
        mix = spec.traffic(name)
        assert callable(spec.part("arrivals", mix["arrivals"]).drive)
        assert callable(spec.part("selectors", mix["selector"]).pick)


def test_every_end_to_end_reader_reads_a_window():
    """Readers kept for later cells read too, so adding their metric takes
    only an entry in BENCHMARK.json."""
    rec = {"latencies_ms": [30.0, 10.0, 20.0], "answered_by_close": 3,
           "seconds": 1.5, "setup_s": 4.0}
    names = sorted(f[:-3] for f in os.listdir(os.path.join(spec.BENCH_DIR,
                                                           "e2e"))
                   if f.endswith(".py"))
    assert {"setup_s", "query_p50_ms", "query_p95_ms",
            "queries_per_s"} <= set(names)
    for name in names:
        assert spec.part("e2e", name).read(rec) > 0


def test_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(spec.ROOT, p))
    names = set()
    for entry in (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
                  + BENCH["per_layer"]):
        assert NAME.match(entry["name"]), entry["name"]
        assert entry["name"] not in names
        names.add(entry["name"])
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        with open(os.path.join(spec.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert all(k in cfg for k in c["reduced"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert len(json.dumps(BENCH)) < 64 * 1024
