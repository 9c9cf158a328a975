"""Benchmark harness — the unified entry for every suite in the tree.

With no arguments: one function per paper table/figure
(``paper_tables.py``), printing ``name,us_per_call,derived`` CSV rows
(measured numbers are CPU; TPU-pod numbers are roofline projections
from paper_projection.py, with the paper's own figures for comparison).

``--suite`` reaches every tier bench from one command and ``--json``
emits one combined BENCH report (the ci_smoke schema, DESIGN.md §13):

    # every suite, full configs, one combined json
    PYTHONPATH=src python benchmarks/run.py --suite all --json BENCH.json

    # a subset, tiny CI-smoke configs
    PYTHONPATH=src python benchmarks/run.py --suite storage,serve --tiny

Suites: paper (paper_tables.py), storage (cold/warm slab
cache + skip-rate), serve (micro-batch sweep), cluster (shard sweep),
ingest (write path). Tier benches run as subprocesses so each gets a
fresh jax runtime; their CSV rows are echoed and collected. This
process itself never loads JAX before starting them: a chip belongs to
one process at a time.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks.ci_smoke import (SUITE_SCRIPTS, TINY, make_env, new_report,
                                 run_script)


def paper_main() -> None:
    """The in-process paper tables/figures (the legacy CSV surface).
    Imported here, not at the top: the suite runner starts every suite
    as a child process, and a parent holding a JAX backend would hold
    the chip its children need."""
    from benchmarks import paper_tables
    paper_tables.main()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--suite", default=None, metavar="TAGS",
                    help="comma list of "
                         f"{','.join(SUITE_SCRIPTS)} or 'all' "
                         "(default: paper benches in-process)")
    ap.add_argument("--json", metavar="PATH", dest="json_out",
                    help="write every suite's rows to one combined "
                         "BENCH json (ci_smoke schema); without "
                         "--suite this runs ALL suites at full config")
    ap.add_argument("--tiny", action="store_true",
                    help="run each suite at the CI-smoke tiny config "
                         "instead of its full defaults")
    args = ap.parse_args()

    if args.suite is None and not args.json_out:
        if args.tiny:
            ap.error("--tiny only applies to the suite runner; pass "
                     "--suite (and/or --json) with it")
        paper_main()            # back-compat: plain CSV on stdout
        return

    tags = list(SUITE_SCRIPTS) if args.suite in (None, "all") \
        else [t.strip() for t in args.suite.split(",") if t.strip()]
    unknown = [t for t in tags if t not in SUITE_SCRIPTS]
    if unknown:
        ap.error(f"unknown suite(s) {unknown}; "
                 f"pick from {list(SUITE_SCRIPTS)}")

    env = make_env()
    report = new_report()
    failed = []
    for tag in tags:
        if tag == "paper":
            argv = []           # a bare run.py prints the paper CSV
        else:
            argv = TINY[tag] if args.tiny else []
        print(f"== {tag} ==")
        entry = run_script(tag, argv, env=env, echo_rows=True)
        report["benches"][tag] = entry
        if entry["returncode"] != 0:
            failed.append(tag)
            sys.stderr.write(entry.get("stderr_tail", ""))
        print(f"[{tag}] {'ok' if entry['returncode'] == 0 else 'CRASH'} "
              f"in {entry['wall_s']:.1f}s, {len(entry['rows'])} rows")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(report, f, indent=1)
        n_rows = sum(len(b["rows"]) for b in report["benches"].values())
        print(f"wrote {args.json_out} ({n_rows} rows)")
    if failed:
        sys.exit(f"benchmark crash in: {', '.join(failed)}")


if __name__ == "__main__":
    main()
