"""Search-service launcher: build/load a corpus, serve queries.

    PYTHONPATH=src python -m repro.launch.search --n-docs 100000 \
        --queries 8 --top-k 10
"""
import argparse
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs.paper_search import SearchConfig
from repro.core import corpus as corpus_lib
from repro.core.engine import PatternSearchEngine
from repro.distributed.meshctx import single_device_ctx
from repro.serve import Query


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-docs", type=int, default=100_000)
    ap.add_argument("--vocab", type=int, default=141_000)
    ap.add_argument("--avg-nnz", type=int, default=60)
    ap.add_argument("--nnz-pad", type=int, default=64)
    ap.add_argument("--queries", type=int, default=4)
    ap.add_argument("--top-k", type=int, default=10)
    ap.add_argument("--backend", choices=["jnp", "pallas", "pallas_packed",
                                          "pallas_fused"],
                    default="jnp")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    dev = jax.devices()[0]

    cfg = SearchConfig(name="service", vocab_size=args.vocab,
                       avg_nnz_per_doc=args.avg_nnz, nnz_pad=args.nnz_pad,
                       top_k=args.top_k)
    print(f"[search] synthesizing {args.n_docs} docs "
          f"(vocab {args.vocab}, ~{args.avg_nnz} nnz/doc)...")
    corpus = corpus_lib.synthesize(args.n_docs, args.vocab, args.avg_nnz,
                                   args.nnz_pad, seed=args.seed)
    eng = PatternSearchEngine(corpus, cfg, single_device_ctx(),
                              backend=args.backend)
    rng = np.random.default_rng(args.seed)
    idxs = rng.integers(0, args.n_docs, args.queries)
    qs = [corpus_lib.make_query(corpus, int(i), cfg.max_query_nnz)
          for i in idxs]
    qi = np.stack([q[0] for q in qs])
    qv = np.stack([q[1] for q in qs])

    batch = Query(qi, qv)
    eng.search(batch)             # warm up / compile
    t0 = time.time()
    res = eng.search(batch)
    dt = time.time() - t0
    print(f"[search] {args.queries} queries x {args.n_docs} docs in "
          f"{dt*1e3:.1f} ms ({args.n_docs*args.queries/dt:.3e} "
          f"doc-query pairs/s on {dev.platform} {dev.device_kind})")
    for l, i in enumerate(idxs):
        hit = "OK" if res.doc_ids[l, 0] == i else "MISS"
        print(f"  q{l} (doc {i}): top1 = doc {res.doc_ids[l, 0]} "
              f"cos {res.scores[l, 0]:.4f} [{hit}]")


if __name__ == "__main__":
    main()
