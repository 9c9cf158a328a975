#!/usr/bin/env python3
"""Find an open-loop cell's knee: one set-up, then one window per offered
rate, each reported as a JSON line.

    python3 bench/sweep.py --workload pubmed-shard32.open --seed 5 \\
        --rates 10,15,20,25,30 --seconds 20

The knee is the highest rate at which completions keep up with arrivals
and the backlog does not grow: few queries still queued at the close, and
the 95th percentile of the window's second half no worse than its first
half's by more than a quarter. The rate written into a traffic file is a
number chosen once from such a sweep on the chip; the benchmark itself
never searches for one. ``--keep-trace PATH`` also traces a short window
at the lowest rate and copies its ``.xplane.pb`` to PATH with a summary of
its planes and lines.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def summarize_trace(path: str) -> None:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            names = [e.name for e in line.events]
            lines.append((line.name, len(names), sorted(set(names))[:6]))
        print(json.dumps({"plane": plane.name, "lines": lines[:12]}),
              flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, queries/s")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--keep-trace", default=None)
    ap.add_argument("--trace-seconds", type=float, default=3.0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="a rehearsal off the chip (with --docs small)")
    ap.add_argument("--docs", type=int, default=None,
                    help="override the configuration's n_docs (rehearsal)")
    args = ap.parse_args(argv)
    import numpy as np
    from bench import harness, spec, trace_reduce
    harness.compile_cache_env(ROOT)
    cell = spec.resolve(args.workload, ROOT)
    if args.docs:
        cell.config = dict(cell.config, n_docs=args.docs)
    s = harness.Setup(cell, args.seed, root=ROOT,
                      require_tpu=not args.allow_cpu)
    rates = [float(r) for r in args.rates.split(",")]
    rng = np.random.default_rng([args.seed, 9])
    for rate in rates:
        mix = dict(cell.traffic, rate_qps=rate)
        w = s.window(mix, args.seconds, rng)
        half = w["t_end"] - args.seconds / 2
        first = [r.latency_ms for r in w["ok"] if r.due < half]
        second = [r.latency_ms for r in w["ok"] if r.due >= half]
        p = harness.percentile
        print(json.dumps({
            "rate_qps": rate, "attempted": len(w["reqs"]),
            "answered": len(w["ok"]), "by_close": len(w["in_window"]),
            "queued_at_close": w["pending"],
            "completed_per_s": len(w["in_window"]) / args.seconds,
            "occupancy": w["requests"] / max(w["batches"], 1),
            "p50_ms": p([r.latency_ms for r in w["ok"]], 50),
            "p95_ms": p([r.latency_ms for r in w["ok"]], 95),
            "p95_first_half_ms": p(first, 95) if first else None,
            "p95_second_half_ms": p(second, 95) if second else None,
            "compiled": w["compiled"]}), flush=True)
    if args.keep_trace:
        mix = dict(cell.traffic, rate_qps=min(rates))
        s.window(mix, args.trace_seconds, rng, trace=True)
        path = trace_reduce.find_xplane(s.trace_dir)
        shutil.copy(path, args.keep_trace)
        summarize_trace(path)
        red = trace_reduce.reduce_trace(path)
        print(json.dumps(red), flush=True)
        shutil.rmtree(s.trace_dir, ignore_errors=True)
    s.close()
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
