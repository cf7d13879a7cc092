"""Stage-1 candidate generation: per-query-token kNN + ANN-derived bounds.

Follows paper App. A.1: for each query token q_t, retrieve the top-k' most
similar document tokens (instantiated as exact kNN for reproducibility, as
in the paper); the candidate set is the union of owning documents. Eq. 15
turns the stage-1 similarities into per-(doc, token) upper bounds:

    a_it = 0
    b_it = h(d_i, t)      if d_i was retrieved for token t  (exact value!)
         = s_k'^(t)       otherwise (the k'-th neighbor similarity)

Note: when any token of d_i is in the top-k' for q_t, the *best* token of
d_i necessarily is too (it has a higher sim), so the scatter-max below
recovers the exact h(d_i, t) for hit cells. ``known_mask/known_vals`` expose
those exact cells so the (beyond-paper) ``prereveal_ann`` option can start
the bandit with them at zero additional cost.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

_NEG = jnp.float32(-3e38)
# Docs per stage-1 scan step: the (T, chunk*L) f32 similarity block is
# 32 MiB per query at T=32, L=128, so a batch of 8 needs about 256 MiB of
# temp next to a resident index.
STAGE1_CHUNK_DOCS = 2048
# f32 rows in one TPU vector tile (8 rows by 128 lanes).
_TILE_ROWS = 8


class CandidateSet(NamedTuple):
    doc_ids: jax.Array      # (N,) i32, -1 padding
    doc_mask: jax.Array     # (N,) bool
    a: jax.Array            # (N, T) lower support
    b: jax.Array            # (N, T) upper support (Eq. 15)
    known_mask: jax.Array   # (N, T) bool — cells whose exact value stage 1 saw
    known_vals: jax.Array   # (N, T) f32
    s_kprime: jax.Array     # (T,) k'-th neighbor similarity per query token

    @property
    def n_candidates(self) -> jax.Array:
        return jnp.sum(self.doc_mask)


def token_topk(index_embs, index_mask, query, kprime: int, chunk_docs: int):
    """Per-query-token top-k' over the index's valid token rows, scanned
    ``chunk_docs`` documents at a time with a running top-k' — the
    (T, C*L) similarity matrix never exists, so a full-size index fits next
    to its scan. Ties keep the lower token position, exactly as one top_k
    over the whole index does (the running list is merged first).

    ``query`` is (..., T, M): every query token of every leading index is
    one row of a single (R, M) scan, and each row is selected on its own.
    Callers pass a batch here rather than vmapping: a vmapped ``top_k`` has
    a rank-3 operand, which the TPU compiler lowers to a full sort of each
    chunk's C*L similarities; a rank-2 one lowers to its TopK. Each chunk
    is pre-selected by its documents' maxima over their L tokens, so no
    TopK is as wide as the chunk's tokens.
    Returns (values (..., T, k'), owning doc ids (..., T, k'))."""
    C, L, M = index_embs.shape
    lead = query.shape[:-1]
    q = query.reshape(-1, M).astype(jnp.float32)                   # (R, M)
    R = q.shape[0]
    G = -(-R // _TILE_ROWS)
    q = jnp.pad(q, ((0, G * _TILE_ROWS - R), (0, 0)))

    def chunk_topk(embs, mask, start):
        n = embs.shape[0]
        sims = q @ embs.reshape(n * L, M).astype(jnp.float32).T    # (R, n*L)
        sims = jnp.where(mask.reshape(-1)[None, :], sims, _NEG)
        # A row's top-k' tokens lie in its k' documents of highest max (a
        # document tied at the k'-th max with a higher id holds only tokens
        # ranked after the k'-th), so select those documents first, then
        # their k'*L tokens in position order: the same values and ties as
        # one top_k over the chunk's n*L columns, from TopKs n and k'*L wide.
        # The block is viewed as the TPU tiles it, _TILE_ROWS rows by L
        # tokens a tile, so neither the max nor the gather of whole tiles
        # copies it; each row then keeps its own row of each gathered tile.
        kd = min(kprime, n)
        rows = sims.reshape(G, _TILE_ROWS, n, L)
        _, docs = jax.lax.top_k(rows.max(axis=3).reshape(-1, n), kd)
        docs = jnp.sort(docs, axis=1)                              # (R, kd)
        tiles = jnp.take_along_axis(rows.transpose(0, 2, 1, 3),
                                    docs.reshape(G, -1)[:, :, None, None],
                                    axis=1)
        tiles = tiles.reshape(G, _TILE_ROWS, kd, _TILE_ROWS, L)
        r = jnp.arange(_TILE_ROWS)
        toks = tiles[jnp.arange(G)[:, None], r, :, r, :]        # (G, 8, kd, L)
        vals, idx = jax.lax.top_k(toks.reshape(-1, kd * L),
                                  min(kprime, kd * L))
        return vals, start + jnp.take_along_axis(docs, idx // L, axis=1)

    def merge(best, new):
        vals, pos = jax.lax.top_k(jnp.concatenate([best[0], new[0]], 1),
                                  kprime)
        docs = jnp.concatenate([best[1], new[1]], 1)
        return vals, jnp.take_along_axis(docs, pos, axis=1)

    with jax.named_scope("stage1_scan"):
        chunk = min(max(chunk_docs, -(-kprime // L)), C)
        n_full, rem = divmod(C, chunk)
        best = chunk_topk(index_embs[:chunk], index_mask[:chunk], 0)
        if n_full > 1:
            def body(best, start):
                e = jax.lax.dynamic_slice_in_dim(index_embs, start, chunk)
                m = jax.lax.dynamic_slice_in_dim(index_mask, start, chunk)
                return merge(best, chunk_topk(e, m, start)), None
            best, _ = jax.lax.scan(
                body, best, jnp.arange(1, n_full, dtype=jnp.int32) * chunk)
        if rem:
            tail = n_full * chunk
            best = merge(best, chunk_topk(index_embs[tail:], index_mask[tail:],
                                          tail))
        vals, docs = best
        return (vals[:R].reshape(*lead, kprime),
                docs[:R].reshape(*lead, kprime))


_STAGE1_STATIC = ("kprime", "max_candidates", "support", "chunk_docs")


@functools.partial(jax.jit, static_argnames=_STAGE1_STATIC)
def generate_candidates(
    index_embs: jax.Array,      # (C, L, M)
    index_mask: jax.Array,      # (C, L)
    query: jax.Array,           # (T, M)
    quota=None,                 # () i32 traced cap on |candidates|, or None
    *,
    kprime: int = 10,
    max_candidates: int = 256,
    support: Tuple[float, float] = (0.0, 1.0),
    chunk_docs: int = STAGE1_CHUNK_DOCS,
) -> CandidateSet:
    C, L, _ = index_embs.shape
    kprime = min(kprime, C * L)   # a tiny shard can't yield k' neighbors
    top_vals, hit_docs = token_topk(index_embs, index_mask, query, kprime,
                                     chunk_docs)                  # (T, k')
    with jax.named_scope("stage1_candidates"):
        return candidates_from_hits(top_vals, hit_docs, C, quota,
                                    max_candidates=max_candidates,
                                    support=support)


@functools.partial(jax.jit, static_argnames=_STAGE1_STATIC)
def generate_candidates_batch(
    index_embs: jax.Array,      # (C, L, M)
    index_mask: jax.Array,      # (C, L)
    queries: jax.Array,         # (B, T, M)
    quotas=None,                # (B,) i32 traced caps on |candidates|, or None
    *,
    kprime: int = 10,
    max_candidates: int = 256,
    support: Tuple[float, float] = (0.0, 1.0),
    chunk_docs: int = STAGE1_CHUNK_DOCS,
) -> CandidateSet:
    """``generate_candidates`` for each of B queries, fields (B, ...): one
    scan over all B*T query-token rows, then each query's candidate set."""
    C, L, _ = index_embs.shape
    kprime = min(kprime, C * L)
    top_vals, hit_docs = token_topk(index_embs, index_mask, queries, kprime,
                                     chunk_docs)               # (B, T, k')
    with jax.named_scope("stage1_candidates"):
        return jax.vmap(functools.partial(
            candidates_from_hits, n_docs=C, max_candidates=max_candidates,
            support=support))(top_vals, hit_docs, quota=quotas)


def candidates_from_hits(top_vals, hit_docs, n_docs: int, quota=None, *,
                         max_candidates: int,
                         support: Tuple[float, float]) -> CandidateSet:
    """Eq. 15 candidate set from per-token top-k' hits (values and owning
    doc ids, (T, k') each, best first) over an ``n_docs``-document index.
    Callers open the ``stage1_candidates`` scope around it, outside any
    vmap, so the device profile groups its operations under that name."""
    C = n_docs
    T, kprime = top_vals.shape
    s_kprime = top_vals[:, kprime - 1]

    # Candidate set = union of hit docs. If the union exceeds
    # max_candidates, keep the docs with the HIGHEST best-hit similarity
    # (arbitrary-id truncation would silently drop strong candidates).
    doc_best = jnp.full((C,), _NEG).at[hit_docs.reshape(-1)].max(
        top_vals.reshape(-1))
    best_vals, best_ids = jax.lax.top_k(doc_best, min(max_candidates, C))
    if C < max_candidates:           # pad to the static candidate count
        pad = max_candidates - C
        best_vals = jnp.pad(best_vals, (0, pad), constant_values=_NEG)
        best_ids = jnp.pad(best_ids, (0, pad), constant_values=0)
    sel = best_vals > _NEG / 2
    if quota is not None:
        # Skew-aware routing cap: best_vals is descending, so rank ==
        # position; keep only the strongest ``quota`` candidates.
        sel = sel & (jnp.arange(max_candidates) < quota)
    sentinel = jnp.iinfo(jnp.int32).max
    sorted_slots = jnp.sort(jnp.where(sel, best_ids, sentinel))
    # Keep the sentinel-padded array around: it stays ascending, which the
    # searchsorted hit-lookup below requires (-1 padding would break the
    # sort order and silently drop exact b-values for high doc ids).
    cands = jnp.where(sorted_slots == sentinel, -1,
                      sorted_slots).astype(jnp.int32)
    doc_mask = cands >= 0

    a_lo, b_hi = support
    a = jnp.full((max_candidates, T), jnp.float32(a_lo))
    # Default upper bound: the k'-th neighbor similarity per token
    # (Eq. 15).
    b = jnp.broadcast_to(jnp.maximum(s_kprime, a_lo)[None, :],
                         (max_candidates, T)).astype(jnp.float32)

    # Hit cells: exact h value via scatter-max into candidate rows.
    pos = jnp.searchsorted(sorted_slots, hit_docs)             # (T, k')
    pos = jnp.clip(pos, 0, max_candidates - 1)
    is_cand = jnp.take(sorted_slots, pos) == hit_docs
    t_grid = jnp.broadcast_to(jnp.arange(T)[:, None], hit_docs.shape)
    safe_pos = jnp.where(is_cand, pos, max_candidates - 1)

    known_vals = jnp.full((max_candidates, T), _NEG)
    known_vals = known_vals.at[safe_pos, t_grid].max(
        jnp.where(is_cand, top_vals, _NEG))
    known_mask = known_vals > _NEG / 2
    known_vals = jnp.where(known_mask, known_vals, 0.0)

    b = jnp.where(known_mask, known_vals, b)
    b = jnp.clip(b, a_lo, b_hi)
    a = jnp.where(doc_mask[:, None], a, 0.0)
    b = jnp.where(doc_mask[:, None], b, 0.0)

    return CandidateSet(doc_ids=cands, doc_mask=doc_mask, a=a, b=b,
                        known_mask=known_mask & doc_mask[:, None],
                        known_vals=known_vals, s_kprime=s_kprime)


def generic_bounds(n: int, t: int,
                   support: Tuple[float, float] = (0.0, 1.0)
                   ) -> Tuple[jax.Array, jax.Array]:
    """No-ANN fallback: global similarity-range bounds (paper Sec. 5.3)."""
    a = jnp.full((n, t), jnp.float32(support[0]))
    b = jnp.full((n, t), jnp.float32(support[1]))
    return a, b
