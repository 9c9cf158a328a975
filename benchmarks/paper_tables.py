"""The paper's tables and figures as in-process benches, printing
``name,us_per_call,derived`` CSV rows: measured rows come from the
host that runs them (the CPU backend); TPU-pod rows are roofline projections from paper_projection.py,
with the paper's own figures for comparison. ``benchmarks/run.py`` (bare,
or its ``paper`` suite) runs ``main``.
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax.numpy as jnp
import numpy as np

from benchmarks import paper_projection as proj
from repro.configs.paper_search import SearchConfig
from repro.core import corpus as corpus_lib
from repro.core.engine import PatternSearchEngine
from repro.core import stream_format as sf
from repro.distributed.meshctx import single_device_ctx
from repro.kernels import ops as kops


def _time(fn, n=5, warmup=2):
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6  # us


def _row(name, us, derived):
    print(f"{name},{us:.1f},{derived}")


# ---------------------------------------------------------------------------
def bench_fig13_docs_per_sec():
    """Fig. 13: document match throughput. Measured: CPU engine (the
    'in-memory CPU' configuration (3) analogue). Projected: TPU pod at
    paper sparsity. Paper: 10.35M docs/s (BlueDBM), 13M docs/s (24-thread
    in-memory)."""
    cfg = SearchConfig(name="bench", vocab_size=141_000, avg_nnz_per_doc=60,
                       nnz_pad=64, doc_tile=128, top_k=16,
                       block_docs=128, block_query=512)
    n_docs = 50_000
    corpus = corpus_lib.synthesize(n_docs, cfg.vocab_size,
                                   cfg.avg_nnz_per_doc, cfg.nnz_pad, seed=1)
    ctx = single_device_ctx()
    eng = PatternSearchEngine(corpus, cfg, ctx, backend="jnp")
    qi, qv = corpus_lib.make_query(corpus, 7, cfg.max_query_nnz)

    us = _time(lambda: eng.search(qi[None], qv[None]), n=3)
    cpu_rate = n_docs / (us / 1e6)
    _row("fig13/engine_cpu_1worker_docs_per_sec", us, f"{cpu_rate:.3e}")

    p0 = proj.project(nnz_pad=128, query_tile=2048, l_queries=1)
    _row("fig13/tpu_pod_paper_faithful_docs_per_sec", 0.0,
         f"{p0.docs_per_sec_pod:.3e} ({p0.bound}-bound; "
         f"{p0.speedup_vs_paper():.0f}x paper's 10.35M/s)")
    p1 = proj.project(nnz_pad=64, query_tile=128, l_queries=1, val_bytes=2)
    _row("fig13/tpu_pod_optimized_packed_docs_per_sec", 0.0,
         f"{p1.docs_per_sec_pod:.3e} ({p1.bound}-bound; Fig.8-packed HBM "
         f"corpus; {p1.speedup_vs_paper():.0f}x paper)")
    return cpu_rate


# ---------------------------------------------------------------------------
def bench_table1_power():
    """Table 1: power. Not measurable here; report the projected docs/J on
    v5e (assumed 200 W/chip) vs the paper's 10.35M docs/s / 120 W."""
    p = proj.project(nnz_pad=64, query_tile=512, l_queries=1)
    paper_eff = proj.PAPER_DOCS_PER_SEC / proj.PAPER_WATTS
    _row("table1/paper_docs_per_joule", 0.0, f"{paper_eff:.3e}")
    _row("table1/tpu_projected_docs_per_joule", 0.0,
         f"{p.docs_per_joule:.3e} ({p.docs_per_joule/paper_eff:.0f}x; "
         f"assumes {proj.ASSUMED_CHIP_WATTS:.0f}W/chip)")


# ---------------------------------------------------------------------------
def bench_table2_scalability():
    """Table 2: kernels 8->20, query batch 1->3: the L-query batching that
    lifts arithmetic intensity. We sweep L and report where the bound flips
    (paper: 10.35M -> 27M docs/s estimated)."""
    for L in (1, 3, 8, 16):
        p = proj.project(nnz_pad=64, query_tile=128, l_queries=L,
                         val_bytes=2)
        _row(f"table2/L={L}_pairs_per_sec_pod", 0.0,
             f"{p.docs_per_sec_pod * L:.3e} ({p.bound}-bound, "
             f"{p.flops_per_doc:.0f} flops/doc)")
    # measured CPU analogue: batched vs single-query scoring time
    cfg = SearchConfig(name="b2", vocab_size=20_000, avg_nnz_per_doc=40,
                       nnz_pad=64, top_k=8, block_docs=128, block_query=256)
    corpus = corpus_lib.synthesize(20_000, cfg.vocab_size,
                                   cfg.avg_nnz_per_doc, cfg.nnz_pad, seed=2)
    ctx = single_device_ctx()
    eng = PatternSearchEngine(corpus, cfg, ctx, backend="jnp")
    qs = [corpus_lib.make_query(corpus, i, cfg.max_query_nnz)
          for i in (1, 2, 3)]
    qi = np.stack([q[0] for q in qs])
    qv = np.stack([q[1] for q in qs])
    us3 = _time(lambda: eng.search(qi, qv), n=3)
    us1 = _time(lambda: eng.search(qi[:1], qv[:1]), n=3)
    _row("table2/cpu_batch3_vs_1_speedup", us3,
         f"{3 * us1 / us3:.2f}x effective")


# ---------------------------------------------------------------------------
def bench_sec5c_partial_products():
    """Sec V.C: partial products/sec at 0.04% sparsity (paper: 13M pp/s =
    8.2M docs x 483M words in 0.8s)."""
    from repro.kernels import ref as kref
    cfg = SearchConfig(name="pp", vocab_size=141_000, avg_nnz_per_doc=60,
                       nnz_pad=64, top_k=8)
    corpus = corpus_lib.synthesize(30_000, cfg.vocab_size,
                                   cfg.avg_nnz_per_doc, cfg.nnz_pad, seed=3)
    qi, qv = corpus_lib.make_query(corpus, 11, 2048)
    mi, mv = kops.merge_queries(qi[None], qv[None])
    pp = int(kref.partial_product_count(
        jnp.asarray(corpus.ids), jnp.asarray(corpus.vals), jnp.asarray(mi),
        jnp.asarray(mv), cfg.vocab_size))
    ctx = single_device_ctx()
    eng = PatternSearchEngine(corpus, cfg, ctx, backend="jnp")
    us = _time(lambda: eng.search(qi[None], qv[None]), n=3)
    cpu_pp_rate = pp / (us / 1e6)
    _row("sec5c/cpu_partial_products_per_sec", us, f"{cpu_pp_rate:.3e}")
    p = proj.project(nnz_pad=64, query_tile=512, l_queries=1)
    tpu_pp = proj.partial_products_per_sec(p.docs_per_sec_pod)
    _row("sec5c/tpu_projected_pp_per_sec", 0.0,
         f"{tpu_pp:.3e} ({tpu_pp/proj.PAPER_PP_PER_SEC:.0f}x paper's 13M/s)")


# ---------------------------------------------------------------------------
def bench_fig8_stream_format():
    """Fig. 8 format: encode/decode throughput + bandwidth saving."""
    rng = np.random.default_rng(0)
    docs = [(d, [(int(w), int(rng.integers(1, 50)))
                 for w in np.sort(rng.choice(141_000, 60, replace=False))])
            for d in range(5000)]
    stream = sf.encode(docs)
    us = _time(lambda: sf.decode_to_ell(stream, 64), n=3)
    rate = stream.nbytes / (us / 1e6) / 1e9
    saving = 1 - sf.stream_bytes(docs) / sf.uci_bytes(docs)
    _row("fig8/decode_to_ell_GBps", us, f"{rate:.2f}")
    _row("fig8/bandwidth_saving_vs_uci", 0.0,
         f"{saving*100:.1f}% (paper claims ~50%)")


# ---------------------------------------------------------------------------
def bench_kernel_sparse_match():
    """Pallas kernel (interpret mode on CPU) vs jnp gather path."""
    cfg = SearchConfig(name="k", vocab_size=10_000, avg_nnz_per_doc=40,
                       nnz_pad=64)
    corpus = corpus_lib.synthesize(4096, cfg.vocab_size, 40, 64, seed=4)
    qi, qv = corpus_lib.make_query(corpus, 5, 512)
    mi, mv = kops.merge_queries(qi[None], qv[None])
    mi = np.pad(mi, (0, 512 - mi.size), constant_values=-2)
    mv = np.pad(mv, ((0, 512 - mv.shape[0]), (0, 0)))
    ids, vals = jnp.asarray(corpus.ids), jnp.asarray(corpus.vals)
    mij, mvj = jnp.asarray(mi), jnp.asarray(mv)

    def jnp_path():
        kops.correlate(ids, vals, mij, mvj, backend="jnp",
                       vocab_size=cfg.vocab_size).block_until_ready()

    us = _time(jnp_path, n=5)
    _row("kernel/jnp_gather_docs_per_sec", us, f"{4096/(us/1e6):.3e}")

    def pallas_path():
        kops.correlate(ids, vals, mij, mvj, backend="pallas",
                       block_docs=128, block_query=512).block_until_ready()

    us2 = _time(pallas_path, n=2, warmup=1)
    _row("kernel/pallas_interpret_docs_per_sec", us2,
         f"{4096/(us2/1e6):.3e} (interpret mode: correctness only)")


def main() -> None:
    """The in-process paper tables/figures (the legacy CSV surface)."""
    print("name,us_per_call,derived")
    bench_fig8_stream_format()
    bench_fig13_docs_per_sec()
    bench_table1_power()
    bench_table2_scalability()
    bench_sec5c_partial_products()
    bench_kernel_sparse_match()
