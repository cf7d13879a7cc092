"""The plain reference: exhaustive MaxSim and a plain stage-1 scan.

Independent of the program: it imports nothing of it and reads only the
index the benchmark made. Scores are exact f32 MaxSim: the f32 query times
the bf16 index rows at ``Precision.HIGHEST`` (the products of an f32 and a
bf16 value are exact in f32), summed over query tokens in f32. The stage-1
scan is a plain per-span ``top_k`` over every valid token row of the index
at the stage-1 precision the configuration states (bf16 query, bf16 index,
f32 accumulation), merged on the host.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK_BYTES = 1 << 29        # f32 candidate rows gathered per reference call


@functools.partial(jax.jit, static_argnames="bf16_query")
def _scores(embs, mask, q, ids, *, bf16_query: bool):
    """(R, T, M) queries, (R, N) ids (-1 pad) -> (R, N) MaxSim scores."""
    safe = jnp.maximum(ids, 0)
    docs = jnp.take(embs, safe, axis=0)                      # (R, N, L, M)
    dmask = jnp.take(mask, safe, axis=0) & (ids >= 0)[..., None]
    if bf16_query:
        sims = jnp.einsum("rtm,rnlm->rntl", q.astype(jnp.bfloat16), docs,
                          preferred_element_type=jnp.float32)
    else:
        sims = jnp.einsum("rtm,rnlm->rntl", q, docs.astype(jnp.float32),
                          precision=HIGHEST)
    h = jnp.max(jnp.where(dmask[:, :, None, :], sims, -jnp.inf), axis=-1)
    s = jnp.sum(jnp.where(jnp.isfinite(h), h, 0.0), axis=-1)
    return jnp.where(ids >= 0, s, -jnp.inf)


def maxsim_scores(embs, mask, queries: Sequence[np.ndarray],
                  cands: Sequence[np.ndarray], *,
                  bf16_query: bool = False) -> List[Dict[int, float]]:
    """Score of every candidate of every request, ``{doc id: score}``,
    computed in blocks of requests so the gathered rows fit."""
    width = max(len(c) for c in cands)
    L, M = embs.shape[1], embs.shape[2]
    R = max(1, min(len(cands), BLOCK_BYTES // (width * L * M * 4)))
    out: List[Dict[int, float]] = []
    for lo in range(0, len(cands), R):
        qs = np.zeros((R,) + queries[0].shape, np.float32)
        ids = np.full((R, width), -1, np.int32)
        for j, (q, c) in enumerate(zip(queries[lo:lo + R], cands[lo:lo + R])):
            qs[j], ids[j, :len(c)] = q, c
        s = np.asarray(_scores(embs, mask, jnp.asarray(qs), jnp.asarray(ids),
                               bf16_query=bf16_query))
        for j, c in enumerate(cands[lo:lo + R]):
            out.append(dict(zip(c.tolist(), s[j, :len(c)].tolist())))
    return out


def topk(scores: Dict[int, float], k: int) -> List[int]:
    """Best ``k`` ids, ties to the lower id."""
    return sorted(scores, key=lambda d: (-scores[d], d))[:k]


@functools.partial(jax.jit, static_argnames="kprime")
def _span_topk(embs, mask, q, *, kprime: int):
    n, L, M = embs.shape
    sims = jnp.einsum("tm,km->tk", q.astype(jnp.bfloat16),
                      embs.reshape(n * L, M),
                      preferred_element_type=jnp.float32)
    sims = jnp.where(mask.reshape(-1)[None, :], sims, -jnp.inf)
    return jax.lax.top_k(sims, kprime)


def stage1_candidates(embs, mask, query: np.ndarray, *, kprime: int,
                      max_candidates: int, span: int) -> np.ndarray:
    """Stage-1 candidates rebuilt by a plain scan: each query token's k'
    most similar valid token rows over the whole index (best first, ties
    to the lower row), the union of their documents, and, where the union
    is larger than ``max_candidates``, the documents with the best hit."""
    C, L, _ = embs.shape
    vals, pos = [], []
    q = jnp.asarray(query)
    for s in range(0, C, span):
        v, p = _span_topk(embs[s:s + span], mask[s:s + span], q,
                          kprime=kprime)
        vals.append(np.asarray(v))
        pos.append(np.asarray(p) + s * L)
    vals, pos = np.concatenate(vals, 1), np.concatenate(pos, 1)
    best: Dict[int, float] = {}
    for v, p in zip(vals, pos):
        order = np.lexsort((p, -v))[:kprime]
        for val, doc in zip(v[order], p[order] // L):
            doc = int(doc)
            if val > best.get(doc, -np.inf):
                best[doc] = float(val)
    keep = sorted(best, key=lambda d: (-best[d], d))[:max_candidates]
    return np.asarray(sorted(keep), np.int32)
