"""Quickstart: Col-Bandit reranking on a synthetic corpus in ~30 lines.

Runs through the unified batched pipeline entrypoint
(``repro.retrieval.pipeline.serve_queries``) — the exact stage-1 +
rerank code path the serving engine AOT-compiles.

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.configs.base import BanditConfig
from repro.data.synthetic import make_retrieval_dataset
from repro.retrieval.index import build_index
from repro.retrieval.pipeline import serve_queries
from repro.launch.compile_cache import enable_compile_cache


def main():
    ds = make_retrieval_dataset(n_docs=256, n_queries=4, seed=0)
    index = build_index(ds.doc_embs, ds.doc_mask, ds.doc_lens)
    queries = np.asarray(ds.queries)                       # (B, T, M)

    dense = serve_queries(index, queries, k=5, flavor="dense")
    bandit = serve_queries(index, queries, k=5, flavor="bandit",
                           bandit=BanditConfig(k=5, alpha_ef=0.3))

    overlap = np.mean([len(set(d) & set(b)) / 5.0
                       for d, b in zip(dense.topk_ids, bandit.topk_ids)])
    print(f"dense top-5 (q0) : {dense.topk_ids[0]}")
    print(f"bandit top-5 (q0): {bandit.topk_ids[0]}")
    print(f"mean overlap@5   : {overlap:.2f}")
    print(f"reveal fraction  : {100 * bandit.reveal_fraction.mean():.1f}% "
          f"of the MaxSim matrix (dense computes 100%)")
    print(f"frontier stats   : occupancy={bandit.stats[0]:.2f} "
          f"rounds={bandit.stats[1]:.0f} "
          f"lockstep_waste={bandit.stats[2]:.0f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
