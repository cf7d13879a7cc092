"""Two-stage late-interaction retrieval pipeline (paper App. A.1).

Stage 1: per-token kNN candidate generation (+ Eq. 15 bounds).
Stage 2: exact or pruned reranking over the candidate MaxSim matrix, with
         method ∈ {exact, bandit (Alg. 1), batched (TPU variant),
         uniform (Alg. 2), topmargin (Alg. 3)}.

Cost accounting follows the paper: the atomic unit is one MaxSim cell
(Sec. 2.1); FLOPs additionally weight each cell by its true document length
(2 * M * L_i per cell), so "coverage" and "MaxSim FLOPs saved" are both
reported.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import BanditConfig
from repro.core import metrics as M
from repro.core.bandit import run_bandit
from repro.core.batched import run_batched_oracle
from repro.core.baselines import doc_top_margin, doc_uniform, exact_topk
from repro.data.synthetic import RetrievalDataset
from repro.kernels import ref as kref
from repro.kernels.ops import maxsim_op
from repro.retrieval.ann import (CandidateSet, generate_candidates,
                                 generate_candidates_batch, generic_bounds)
from repro.retrieval.index import TokenIndex, build_index
from repro.retrieval.service import rerank_bandit_step, rerank_dense_step


@dataclasses.dataclass
class RerankResult:
    topk_docs: np.ndarray        # (K,) global doc ids
    coverage: float              # Eq. 6
    flops: float                 # MaxSim FLOPs actually spent
    flops_exact: float           # FLOPs of full reranking
    overlap: float               # Eq. 16 vs exact rerank
    metrics: Dict[str, float]    # recall/mrr/ndcg vs qrels (if given)
    rounds: int = 0
    separated: bool = True


def _cell_flops(doc_lens: jax.Array, revealed: jax.Array, dim: int) -> jax.Array:
    """FLOPs = sum over revealed cells of 2*M*L_i."""
    per_doc = revealed.sum(axis=-1).astype(jnp.float32)       # cells per doc
    return jnp.sum(per_doc * doc_lens.astype(jnp.float32)) * 2.0 * dim


def rerank_query(
    index: TokenIndex,
    query: jax.Array,                 # (T, M)
    *,
    method: str = "bandit",
    k: int = 5,
    bandit: Optional[BanditConfig] = None,
    use_ann_bounds: bool = True,
    prereveal_ann: bool = False,      # beyond-paper: seed with stage-1 cells
    budget_fraction: float = 0.25,    # for the static baselines
    kprime: int = 10,
    max_candidates: int = 256,
    use_kernel: bool = False,
    qrels_row: Optional[np.ndarray] = None,
    seed: int = 0,
) -> RerankResult:
    bandit = bandit or BanditConfig(k=k)
    T = query.shape[0]
    cand = generate_candidates(index.doc_embs, index.doc_mask, query,
                               kprime=kprime, max_candidates=max_candidates,
                               support=bandit.support)
    embs, tok_mask = index.gather_docs(cand.doc_ids)
    if use_kernel:
        h_full = maxsim_op(embs, tok_mask, query)
    else:
        h_full = kref.maxsim_ref(embs, tok_mask, query)
    h_full = jnp.where(cand.doc_mask[:, None], h_full, 0.0)

    if use_ann_bounds:
        a, b = cand.a, cand.b
    else:
        a, b = generic_bounds(*h_full.shape, support=bandit.support)
        a = jnp.where(cand.doc_mask[:, None], a, 0.0)
        b = jnp.where(cand.doc_mask[:, None], b, 0.0)

    exact_idx, _ = exact_topk(h_full, k=k, doc_mask=cand.doc_mask)
    doc_lens = jnp.take(index.doc_lens, jnp.maximum(cand.doc_ids, 0))
    doc_lens = jnp.where(cand.doc_mask, doc_lens, 0)
    flops_exact = float(_cell_flops(
        doc_lens, jnp.broadcast_to(cand.doc_mask[:, None], h_full.shape),
        index.dim))

    key = jax.random.key(seed)
    rounds, separated = 0, True
    if method == "exact":
        topk_hat = exact_idx
        revealed = jnp.broadcast_to(cand.doc_mask[:, None], h_full.shape)
        coverage = 1.0
    elif method == "bandit":
        # Beyond-paper option: stage-1 already computed some cells exactly —
        # reveal them for free before the LUCB loop starts.
        res = run_bandit(
            h_full, a, b, key, k=k, delta=bandit.delta,
            alpha_ef=bandit.alpha_ef, epsilon=bandit.epsilon,
            radius_c=bandit.radius_c, bias_kappa=bandit.bias_kappa,
            warmup_fraction=bandit.warmup_fraction,
            doc_mask=cand.doc_mask,
            init_one_per_doc=not prereveal_ann,
            prereveal=cand.known_mask if prereveal_ann else None)
        topk_hat, revealed = res.topk, res.revealed
        if prereveal_ann:
            # stage-1 cells cost nothing; subtract them from the bill
            revealed = res.revealed & ~cand.known_mask
        coverage = float(res.coverage)
        rounds, separated = int(res.rounds), bool(res.separated)
    elif method == "batched":
        res = run_batched_oracle(
            h_full, a, b, key, k=k, delta=bandit.delta,
            alpha_ef=bandit.alpha_ef, epsilon=bandit.epsilon,
            radius_c=bandit.radius_c, bias_kappa=bandit.bias_kappa,
            block_docs=bandit.block_docs,
            block_tokens=bandit.block_tokens, doc_mask=cand.doc_mask)
        topk_hat, revealed = res.topk, res.revealed
        coverage = float(res.coverage)
        rounds, separated = int(res.rounds), bool(res.separated)
    elif method == "uniform":
        res = doc_uniform(h_full, key, k=k,
                          budget=max(1, int(budget_fraction * T)),
                          doc_mask=cand.doc_mask)
        topk_hat, revealed, coverage = res.topk, res.revealed, float(res.coverage)
    elif method == "topmargin":
        res = doc_top_margin(h_full, a, b, k=k,
                             budget=max(1, int(budget_fraction * T)),
                             doc_mask=cand.doc_mask)
        topk_hat, revealed, coverage = res.topk, res.revealed, float(res.coverage)
    else:
        raise ValueError(f"unknown method {method!r}")

    flops = float(_cell_flops(doc_lens, revealed, index.dim))
    overlap = float(M.overlap_at_k(topk_hat, exact_idx))

    topk_docs = np.asarray(jnp.take(cand.doc_ids, topk_hat))
    task_metrics: Dict[str, float] = {}
    if qrels_row is not None:
        rel = jnp.asarray(qrels_row)
        rel_cand = jnp.where(cand.doc_mask, rel[jnp.maximum(cand.doc_ids, 0)],
                             False)
        task_metrics = {
            "recall": float(M.recall_at_k(topk_hat, rel_cand)),
            "mrr": float(M.mrr_at_k(topk_hat, rel_cand)),
            "ndcg": float(M.ndcg_at_k(topk_hat, rel_cand)),
        }
    return RerankResult(topk_docs=topk_docs, coverage=coverage, flops=flops,
                        flops_exact=flops_exact, overlap=overlap,
                        metrics=task_metrics, rounds=rounds,
                        separated=separated)


@dataclasses.dataclass
class ServeResult:
    """Batched pipeline output (numpy, ready for the caller)."""

    topk_scores: np.ndarray      # (B, K) f32
    topk_ids: np.ndarray         # (B, K) global doc ids, -1 padded
    reveal_fraction: np.ndarray  # (B,) fraction of MaxSim cells computed
    stats: np.ndarray            # (3,) [occupancy, rounds, lockstep waste]


def serve_queries(
    index,
    queries,                     # (B, T, M)
    *,
    k: int = 5,
    flavor: str = "bandit",      # "dense" | "bandit"
    kprime: int = 10,
    max_candidates: int = 64,
    bandit: Optional[BanditConfig] = None,
    engine: str = "pooled",
    max_rounds: int = -1,
    seed: int = 0,
) -> ServeResult:
    """The unified batched pipeline entrypoint: stage-1 kNN + Eq. 15 bounds
    feeding the SAME engine-facing rerank steps ``RetrievalEngine``
    AOT-compiles (``service.rerank_dense_step`` / ``rerank_bandit_step``) —
    what the examples run is what the engine serves.

    ``index`` is duck-typed: a ``TokenIndex`` (``doc_embs``/``doc_mask``),
    a ``repro.retrieval.corpus.Corpus`` facade, or any object exposing
    ``embs``/``mask``. (:func:`rerank_query` remains the single-query
    research harness with the full method zoo and FLOP accounting.)"""
    embs = getattr(index, "embs", None)
    mask = getattr(index, "mask", None)
    if embs is None:
        embs, mask = index.doc_embs, index.doc_mask
    bandit = bandit or BanditConfig(k=k)
    queries = jnp.asarray(queries, jnp.float32)

    cand = generate_candidates_batch(embs, mask, queries, kprime=kprime,
                                     max_candidates=max_candidates,
                                     support=bandit.support)
    key = jax.random.key(seed)
    if flavor == "dense":
        scores, gids, frac, stats = rerank_dense_step(
            embs, mask, queries, cand.doc_ids, cand.a, cand.b, key, topk=k)
    elif flavor == "bandit":
        scores, gids, frac, stats = rerank_bandit_step(
            embs, mask, queries, cand.doc_ids, cand.a, cand.b, key, topk=k,
            alpha_ef=bandit.alpha_ef, delta=bandit.delta,
            block_docs=bandit.block_docs, block_tokens=bandit.block_tokens,
            max_rounds=max_rounds, engine=engine)
    else:
        raise ValueError(f"unknown serving flavor {flavor!r}")
    return ServeResult(topk_scores=np.asarray(scores),
                       topk_ids=np.asarray(gids),
                       reveal_fraction=np.asarray(frac),
                       stats=np.asarray(stats))


def evaluate_dataset(
    dataset: RetrievalDataset,
    *,
    method: str = "bandit",
    k: int = 5,
    bandit: Optional[BanditConfig] = None,
    **kw,
) -> Dict[str, float]:
    """Mean coverage / overlap / task metrics over all queries."""
    index = build_index(dataset.doc_embs, dataset.doc_mask, dataset.doc_lens)
    rows = []
    for qi in range(dataset.n_queries):
        r = rerank_query(index, jnp.asarray(dataset.queries[qi]),
                         method=method, k=k, bandit=bandit,
                         qrels_row=dataset.qrels[qi], seed=qi, **kw)
        rows.append(r)
    out = {
        "coverage": float(np.mean([r.coverage for r in rows])),
        "coverage_std": float(np.std([r.coverage for r in rows])),
        "overlap": float(np.mean([r.overlap for r in rows])),
        "flops_saving": float(np.mean(
            [r.flops_exact / max(r.flops, 1.0) for r in rows])),
    }
    if rows and rows[0].metrics:
        for key in rows[0].metrics:
            out[key] = float(np.mean([r.metrics[key] for r in rows]))
    return out
