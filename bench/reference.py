"""The plain reference: exhaustive MaxSim and a plain stage-1 scan.

Independent of the program: it imports nothing of it and reads only the
index the benchmark made. Scores are exact f32 MaxSim: the f32 query times
the bf16 index rows at ``Precision.HIGHEST`` (the products of an f32 and a
bf16 value are exact in f32), summed over query tokens in f32. The stage-1
scan is a plain per-span ``top_k`` over every valid token row of the index
at the stage-1 precision the configuration states (bf16 query, bf16 index,
f32 accumulation), merged on the host.

An index sharded over several chips is read where it lies: each chip's
block of rows scores and scans its own documents, and the host merges.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

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


def blocks(embs, mask) -> List[Tuple[int, int, jax.Array, jax.Array]]:
    """(first doc, end doc, rows, mask) of each device's block of the
    index, in row order: one block for an index on one device."""
    start = lambda s: s.index[0].start or 0
    return [(start(e), start(e) + e.data.shape[0], e.data, m.data)
            for e, m in zip(sorted(embs.addressable_shards, key=start),
                            sorted(mask.addressable_shards, key=start))]


def maxsim_scores(embs, mask, queries: Sequence[np.ndarray],
                  cands: Sequence[np.ndarray], *,
                  bf16_query: bool = False) -> List[Dict[int, float]]:
    """Score of every candidate of every request, ``{doc id: score}``,
    each scored on the device that holds it, in blocks of requests so the
    gathered rows fit."""
    width = max(len(c) for c in cands)
    L, M = embs.shape[1], embs.shape[2]
    R = max(1, min(len(cands), BLOCK_BYTES // (width * L * M * 4)))
    out: List[Dict[int, float]] = [{} for _ in cands]
    for lo in range(0, len(cands), R):
        pending = []
        for first, end, e, m in blocks(embs, mask):
            qs = np.zeros((R,) + queries[0].shape, np.float32)
            ids = np.full((R, width), -1, np.int32)
            mine = []
            for j, (q, c) in enumerate(zip(queries[lo:lo + R],
                                           cands[lo:lo + R])):
                c = c[(c >= first) & (c < end)]
                qs[j], ids[j, :len(c)] = q, c - first
                mine.append(c)
            if any(len(c) for c in mine):
                dev = e.devices().pop()
                pending.append((mine, _scores(
                    e, m, jax.device_put(qs, dev), jax.device_put(ids, dev),
                    bf16_query=bf16_query)))
        for mine, s in pending:
            s = np.asarray(s)
            for j, c in enumerate(mine):
                out[lo + j].update(zip(c.tolist(), s[j, :len(c)].tolist()))
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


def _token_hits(parts, query: np.ndarray, kprime: int, span: int):
    """Each query token's ``kprime`` best valid token rows of each block in
    ``parts`` (span by span, every block's spans launched before any is
    read), as (values, global row positions) per block."""
    launched = []
    for first, end, e, m in parts:
        L = e.shape[1]
        kp = min(kprime, (end - first) * L)
        q = jax.device_put(query, e.devices().pop())
        launched.append([(_span_topk(e[s:s + span], m[s:s + span], q,
                                     kprime=kp), (first + s) * L)
                         for s in range(0, end - first, span)])
    return [(np.concatenate([np.asarray(v) for (v, _), _ in spans], 1),
             np.concatenate([np.asarray(p) + off for (_, p), off in spans],
                            1))
            for spans in launched]


def _candidate_rule(vals, pos, L: int, kprime: int,
                    max_candidates: int) -> List[int]:
    """Each query token's k' best rows (best first, ties to the lower
    row), the union of their documents, and, where the union is larger
    than ``max_candidates``, the documents with the best hit."""
    best: Dict[int, float] = {}
    for v, p in zip(vals, pos):
        order = np.lexsort((p, -v))[:kprime]
        for val, doc in zip(v[order], p[order] // L):
            doc = int(doc)
            if val > best.get(doc, -np.inf):
                best[doc] = float(val)
    return sorted(best, key=lambda d: (-best[d], d))[:max_candidates]


def stage1_candidates(embs, mask, query: np.ndarray, *, kprime: int,
                      max_candidates: int, span: int,
                      scope: str = "index") -> np.ndarray:
    """Stage-1 candidates rebuilt by a plain scan. ``scope="index"``: the
    candidate rule over the whole index. ``scope="shard"``: the rule over
    each device block's own rows (k' hits per token, up to
    ``max_candidates`` documents per block), and the union over the
    blocks."""
    parts = blocks(embs, mask)
    L = embs.shape[1]
    hits = _token_hits(parts, query, kprime, span)
    if scope == "index":
        vals = np.concatenate([v for v, _ in hits], 1)
        pos = np.concatenate([p for _, p in hits], 1)
        keep = _candidate_rule(vals, pos, L, kprime, max_candidates)
    elif scope == "shard":
        keep = [d for (first, end, _, _), (v, p) in zip(parts, hits)
                for d in _candidate_rule(v, p, L, min(kprime, (end - first)
                                                      * L), max_candidates)]
    else:
        raise ValueError(f"unknown stage-1 scope {scope!r}")
    return np.asarray(sorted(keep), np.int32)
