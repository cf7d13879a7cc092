"""End-to-end serving driver: build an index, then serve BATCHED queries
through the unified two-stage pipeline (``serve_queries``) with exact vs
Col-Bandit reranking — the same engine-facing rerank steps
``repro.serve.RetrievalEngine`` AOT-compiles.

  PYTHONPATH=src python examples/serve_retrieval.py [--n-docs 512]
"""
import argparse
import time

import numpy as np

from repro.configs.base import BanditConfig
from repro.data.synthetic import make_retrieval_dataset
from repro.retrieval.index import build_index
from repro.retrieval.pipeline import serve_queries
from repro.launch.compile_cache import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-docs", type=int, default=512)
    ap.add_argument("--n-queries", type=int, default=16)
    ap.add_argument("--alpha", type=float, default=0.3)
    args = ap.parse_args(argv)

    print(f"building index: {args.n_docs} docs ...")
    ds = make_retrieval_dataset(n_docs=args.n_docs, n_queries=args.n_queries,
                                seed=1)
    index = build_index(ds.doc_embs, ds.doc_mask, ds.doc_lens)
    queries = np.asarray(ds.queries)                       # (B, T, M)

    t0 = time.time()
    dense = serve_queries(index, queries, k=5, flavor="dense")
    bandit = serve_queries(index, queries, k=5, flavor="bandit",
                           bandit=BanditConfig(k=5, alpha_ef=args.alpha))
    dt = time.time() - t0

    overlaps = []
    for qi in range(ds.n_queries):
        ov = len(set(dense.topk_ids[qi]) & set(bandit.topk_ids[qi])) / 5.0
        overlaps.append(ov)
        rel = set(np.nonzero(ds.qrels[qi])[0])
        rec = len(rel & set(int(d) for d in bandit.topk_ids[qi]
                            if d >= 0)) / max(len(rel), 1)
        print(f"  q{qi:02d}: overlap={ov:.2f} "
              f"coverage={100 * bandit.reveal_fraction[qi]:4.1f}% "
              f"recall@5={rec:.2f}")

    print(f"\nserved {ds.n_queries} queries in {dt:.1f}s: "
          f"mean coverage {100 * bandit.reveal_fraction.mean():.1f}%, "
          f"mean overlap@5 {np.mean(overlaps):.2f}, "
          f"frontier occupancy {bandit.stats[0]:.2f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()