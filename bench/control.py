#!/usr/bin/env python3
"""The control of a cell's correctness check: its configuration's plain
reference computed one precision lower (bfloat16, on the chip) and put in
the program's place, judged by the same numbers and limits as a run.

    python3 bench/control.py --workload pubmed-shard32.open --seeds 1,2,3

For each seed it makes the cell's corpus at its own size, draws the
run's number of "more like this" queries (``check_sample``) from the
seed, and prints one JSON line: the numbers, each beside its limit, and
whether the control was judged correct (it must not be). Where a limit
is set, the smallest reading the control gives is its upper end; see
PERF.md. The benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def control(cell, seed: int) -> dict:
    import numpy as np
    from bench import spec
    from bench.harness import _stack
    config = cell.config
    gen = spec.part("generators", config["generator"])
    ref = spec.part("references", config["reference"])
    corpus = gen.generate(config, seed)
    rng = np.random.default_rng([seed, 4])
    docs = rng.integers(0, corpus.n_docs, int(cell.traffic["check_sample"]))
    make = getattr(gen, cell.traffic["queries"])
    q_ids, q_vals = _stack([make(corpus, int(d)) for d in docs])
    vocab, k = int(config["vocab_size"]), int(config["top_k"])
    got_i, got_s = ref.control_topk(corpus.ids, corpus.vals, q_ids, q_vals,
                                    vocab, k)
    plain = ref.Reference(corpus.ids, corpus.vals, vocab)
    parts = []
    for lo in range(0, docs.size, 8):
        cos = plain.cos(q_ids[lo:lo + 8], q_vals[lo:lo + 8])
        parts.append(ref.judge(got_i[lo:lo + 8], got_s[lo:lo + 8], cos, k,
                               docs[lo:lo + 8]))
    checks = {key: {"value": v, "limit": ref.LIMITS[key]}
              for key, v in ref.merge_judgements(parts).items()}
    return {"seed": seed, "checks": checks,
            "correct": all(c["value"] <= c["limit"]
                           for c in checks.values())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    args = ap.parse_args(argv)
    import jax
    from bench import spec
    d0 = jax.devices()[0]
    cell = spec.resolve(args.workload, ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = control(cell, seed)
        out["device"] = {"platform": d0.platform, "kind": d0.device_kind}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
