"""Shared harness for the paper-table benchmarks.

All benchmarks run the two-stage pipeline on the synthetic topic-model
corpus (data/synthetic.py) and report the same quantities as the paper:
coverage (Eq. 6), Overlap@K (Eq. 16), Recall/MRR/nDCG@K, and FLOP savings
vs. full reranking. Col-Bandit operating points come from sweeping the
relaxation parameter alpha_ef (paper Sec. 5.1); baseline points from fixed
coverage budgets.
"""
from __future__ import annotations

import functools
import os
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.configs.base import BanditConfig
from repro.data.synthetic import RetrievalDataset, make_retrieval_dataset
from repro.retrieval.pipeline import evaluate_dataset

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_ALPHAS = (0.05, 0.15, 0.3, 0.6, 1.0, 2.0)
DEFAULT_BUDGETS = (0.1, 0.2, 0.3, 0.4, 0.5, 0.65, 0.8, 1.0)


def cpu_worker_env(n_devices: int) -> Dict[str, str]:
    """Environment for a worker process on ``n_devices`` virtual CPU
    devices. Only under the CPU setup (``JAX_PLATFORMS=cpu``): on a chip
    machine the launching process has already used JAX and holds the chip,
    and a CPU worker's timings would pass for the chip's. There, run the
    worker's ``--worker`` entry point directly, in a process of its own."""
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        raise RuntimeError(
            "this phase starts a worker on virtual CPU devices and runs only "
            "under JAX_PLATFORMS=cpu; on a chip machine run its --worker "
            "entry point directly, in a process of its own")
    path = [os.path.join(_ROOT, "src"), _ROOT, os.environ.get("PYTHONPATH", "")]
    return dict(os.environ,
                XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}",
                PYTHONPATH=os.pathsep.join(path).rstrip(os.pathsep))


@functools.lru_cache(maxsize=4)
def bench_dataset(n_docs: int = 384, n_queries: int = 12,
                  seed: int = 7) -> RetrievalDataset:
    return make_retrieval_dataset(n_docs=n_docs, n_queries=n_queries,
                                  distractors_per_query=32, seed=seed)


def frontier_bandit(ds: RetrievalDataset, *, k: int, method: str = "bandit",
                    alphas: Sequence[float] = DEFAULT_ALPHAS,
                    use_ann_bounds: bool = True, epsilon: float = 0.1,
                    warmup_fraction: float = 0.0,
                    init_one_per_doc: bool = True,
                    bias_kappa: float = 0.25,
                    prereveal_ann: bool = False) -> List[Dict]:
    """One operating point per alpha_ef (paper Fig. 2 star markers).
    bias_kappa=0 reproduces the paper's exact Eq. 12 radius."""
    pts = []
    for alpha in alphas:
        cfg = BanditConfig(k=k, alpha_ef=alpha, epsilon=epsilon,
                           warmup_fraction=warmup_fraction,
                           bias_kappa=bias_kappa)
        out = evaluate_dataset(ds, method=method, k=k, bandit=cfg,
                               use_ann_bounds=use_ann_bounds,
                               prereveal_ann=prereveal_ann)
        out["alpha_ef"] = alpha
        pts.append(out)
    return pts


def frontier_budget(ds: RetrievalDataset, *, k: int, method: str,
                    budgets: Sequence[float] = DEFAULT_BUDGETS,
                    use_ann_bounds: bool = True) -> List[Dict]:
    pts = []
    for frac in budgets:
        out = evaluate_dataset(ds, method=method, k=k,
                               budget_fraction=frac,
                               use_ann_bounds=use_ann_bounds)
        out["budget"] = frac
        pts.append(out)
    return pts


def coverage_for_target(points: List[Dict], target_overlap: float
                        ) -> Optional[float]:
    """Min mean coverage among operating points reaching the target
    (paper Table 1: 'coverage budget required to achieve X% Overlap@K')."""
    ok = [p["coverage"] for p in points if p["overlap"] >= target_overlap]
    return min(ok) if ok else None


def fmt_cov(c: Optional[float]) -> str:
    return f"{100 * c:5.1f}%" if c is not None else "  >100%"


def savings(c: Optional[float]) -> str:
    return f"{1.0 / c:4.1f}x" if c else "  - "
