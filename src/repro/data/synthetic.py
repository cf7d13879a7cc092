"""Synthetic late-interaction corpora with controllable relevance structure.

BEIR/REAL-MM-RAG cannot ship in this container, so experiments run on a
topic-model generator that preserves the statistics that matter for
Col-Bandit: (i) normalized token embeddings (cosine MaxSim in [-1, 1], and
in ~[0, 1] for matching topics), (ii) a small set of truly relevant
documents per query whose MaxSim rows dominate, (iii) a long tail of
near-miss distractors that cluster near the decision boundary (these are
what make adaptive allocation pay off), and (iv) variable document lengths.

Every generator is seeded so the data pipeline stays deterministic across
restarts. The host generators return plain numpy (converted lazily to jnp
by consumers); :func:`make_device_corpus` draws the same topic model on
the accelerator, chunk by chunk, for index sizes a host could not build
in reasonable time.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

# Topic-model constants shared by the host and the device generators.
N_TOPICS = 32
TOPIC_STRENGTH = 0.7         # planted relevant tokens' pull toward the topic
DISTRACTOR_STRENGTH = 0.55   # planted near-miss tokens' pull


@dataclasses.dataclass
class RetrievalDataset:
    doc_embs: np.ndarray       # (C, L, M) float32, L2-normalized tokens
    doc_mask: np.ndarray       # (C, L) bool
    doc_lens: np.ndarray       # (C,) int32
    queries: np.ndarray        # (Q, T, M) float32
    qrels: np.ndarray          # (Q, C) bool — relevance labels
    topics: np.ndarray         # (K_topics, M)

    @property
    def n_docs(self) -> int:
        return self.doc_embs.shape[0]

    @property
    def n_queries(self) -> int:
        return self.queries.shape[0]


def _normalize(x: np.ndarray) -> np.ndarray:
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-9)


def make_retrieval_dataset(
    *,
    n_docs: int = 512,
    n_queries: int = 16,
    n_topics: int = N_TOPICS,
    doc_len: int = 96,
    min_doc_len: int = 24,
    query_len: int = 32,
    dim: int = 128,
    relevant_per_query: int = 4,
    distractors_per_query: int = 24,
    topic_strength: float = TOPIC_STRENGTH,
    distractor_strength: float = DISTRACTOR_STRENGTH,
    seed: int = 0,
) -> RetrievalDataset:
    """Topic-model corpus.

    Each doc draws a primary topic; its tokens mix the topic direction with
    noise. A query targets one topic; `relevant_per_query` docs share it
    strongly, `distractors_per_query` share it weakly (borderline scores).
    """
    rng = np.random.default_rng(seed)
    topics = _normalize(rng.standard_normal((n_topics, dim)).astype(np.float32))

    doc_topic = rng.integers(0, n_topics, size=n_docs)
    doc_lens = rng.integers(min_doc_len, doc_len + 1, size=n_docs).astype(np.int32)
    noise = rng.standard_normal((n_docs, doc_len, dim)).astype(np.float32)
    mix = rng.uniform(0.1, 0.5, size=(n_docs, doc_len, 1)).astype(np.float32)
    doc_embs = _normalize(mix * topics[doc_topic][:, None, :] + (1 - mix) * noise * 0.4)
    doc_mask = np.arange(doc_len)[None, :] < doc_lens[:, None]
    doc_embs = np.where(doc_mask[:, :, None], doc_embs, 0.0).astype(np.float32)

    queries = np.zeros((n_queries, query_len, dim), np.float32)
    qrels = np.zeros((n_queries, n_docs), bool)
    for q in range(n_queries):
        topic = rng.integers(0, n_topics)
        qn = rng.standard_normal((query_len, dim)).astype(np.float32)
        # Real queries mix on-topic terms with generic/function tokens, so
        # per-row MaxSim values VARY — the within-row variance that the
        # empirical-Bernstein radius feeds on. ~25% of tokens are pure noise
        # ("stopwords"), the rest span weak-to-strong topicality.
        qmix = rng.uniform(0.15, 0.95, size=(query_len, 1)).astype(np.float32)
        noise_tok = rng.random(query_len) < 0.25
        qmix[noise_tok] = 0.0
        queries[q] = _normalize(qmix * topics[topic][None, :] + (1 - qmix) * qn * 0.4)

        # plant relevant docs: strengthen topic alignment of a random subset
        rel = rng.choice(n_docs, size=relevant_per_query, replace=False)
        for d in rel:
            ln = doc_lens[d]
            n_strong = max(2, int(topic_strength * min(ln, 16)))
            pos = rng.choice(ln, size=n_strong, replace=False)
            tn = rng.standard_normal((n_strong, dim)).astype(np.float32)
            doc_embs[d, pos] = _normalize(
                topic_strength * topics[topic][None, :] + (1 - topic_strength) * tn * 0.3)
        qrels[q, rel] = True

        # borderline distractors: weakly aligned, crowd the boundary
        pool = np.setdiff1d(np.arange(n_docs), rel)
        dis = rng.choice(pool, size=min(distractors_per_query, pool.size),
                         replace=False)
        for d in dis:
            ln = doc_lens[d]
            n_weak = max(1, int(0.3 * min(ln, 12)))
            pos = rng.choice(ln, size=n_weak, replace=False)
            tn = rng.standard_normal((n_weak, dim)).astype(np.float32)
            doc_embs[d, pos] = _normalize(
                distractor_strength * topics[topic][None, :]
                + (1 - distractor_strength) * tn * 0.4)

    doc_embs = np.where(doc_mask[:, :, None], doc_embs, 0.0).astype(np.float32)
    return RetrievalDataset(doc_embs=doc_embs, doc_mask=doc_mask,
                            doc_lens=doc_lens, queries=queries, qrels=qrels,
                            topics=topics)


def make_mixed_difficulty_h(n_queries: int, n_docs: int, n_tokens: int, *,
                            k: int = 10, hard_frac: float = 0.25,
                            seed: int = 0) -> np.ndarray:
    """Oracle MaxSim tensor H (Q, N, T) with a controlled difficulty mix.

    Most queries have their top-k separated by a wide margin at rank k
    (the bandit separates them in few rounds); the last ``hard_frac`` of
    queries have ~2k near-tied contenders straddling rank k (many rounds).
    This is the straggler mix that makes lockstep reveal waste visible —
    shared by the frontier-retirement tests and the reveal benchmark so
    the workload they pin is one and the same.
    """
    rng = np.random.default_rng(seed)
    H = rng.uniform(0.1, 0.4,
                    (n_queries, n_docs, n_tokens)).astype(np.float32)
    n_hard = int(round(hard_frac * n_queries))   # 0.0 -> all-easy batch
    for q in range(n_queries):
        if q < n_queries - n_hard:               # easy: clear top-k margin
            H[q, rng.choice(n_docs, k, replace=False)] += 0.5
        else:                                    # hard: 2k near-ties
            H[q, rng.choice(n_docs, 2 * k, replace=False)] += 0.3
    return np.clip(H, 0.0, 1.0)


# ---------------------------------------------------------------------------
# Device-resident corpus at deployment scale
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DeviceCorpus:
    """A topic-model corpus generated on the device, plus its host-side
    relevance structure. ``embs`` is bf16 and laid out with the sharding
    it was generated under; everything else is small host numpy."""

    embs: jax.Array            # (C, L, M) bf16, zero past each doc's length
    mask: jax.Array            # (C, L) bool
    doc_lens: np.ndarray       # (C,) int32
    doc_topic: np.ndarray      # (C,) int32
    topics: np.ndarray         # (K_topics, M) f32 unit rows
    queries: np.ndarray        # (Q, T, M) f32 unit rows
    query_topic: np.ndarray    # (Q,) int32
    relevant: np.ndarray       # (Q, relevant_per_query) planted relevant ids
    distractors: np.ndarray    # (Q, distractors_per_query) planted near-misses

    @property
    def n_docs(self) -> int:
        return self.embs.shape[0]


def _unit(x):
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-9)


def _draw_docs(key, topics, doc_topic, doc_lens, p_topic, p_strength,
               p_noise, p_count, *, doc_len: int):
    """Token rows for one chunk of docs — the statistics of
    :func:`make_retrieval_dataset`: each token mixes its doc's topic
    (weight U(0.1, 0.5)) with noise; a planted doc additionally gets about
    ``p_count`` tokens pulled hard toward ``p_topic``."""
    n, M = doc_topic.shape[0], topics.shape[1]
    k_noise, k_mix, k_pos, k_plant = jax.random.split(key, 4)
    noise = jax.random.normal(k_noise, (n, doc_len, M), jnp.float32)
    mix = jax.random.uniform(k_mix, (n, doc_len, 1), jnp.float32, 0.1, 0.5)
    e = _unit(mix * topics[doc_topic][:, None, :] + (1 - mix) * noise * 0.4)
    pos = jnp.arange(doc_len)[None, :]
    mask = pos < doc_lens[:, None]
    lens = jnp.maximum(doc_lens, 1).astype(jnp.float32)[:, None]
    u = jax.random.uniform(k_pos, (n, doc_len))
    planted = mask & (p_topic[:, None] >= 0) & (u * lens < p_count[:, None])
    tn = jax.random.normal(k_plant, (n, doc_len, M), jnp.float32)
    s = p_strength[:, None, None]
    strong = _unit(s * topics[jnp.maximum(p_topic, 0)][:, None, :]
                   + (1 - s) * tn * p_noise[:, None, None])
    e = jnp.where(planted[:, :, None], strong, e)
    e = jnp.where(mask[:, :, None], e, 0.0)
    return e.astype(jnp.bfloat16), mask


def _zeros_on(dev, shape, dtype) -> jax.Array:
    """A zero buffer made on ``dev`` itself. ``jnp.zeros(device=dev)``
    broadcasts on the default device and copies the result over, so the
    default device would briefly hold every other device's share too."""
    return jax.jit(lambda: jnp.zeros(shape, dtype),
                   out_shardings=SingleDeviceSharding(dev))()


@functools.partial(jax.jit, static_argnames=("doc_len",),
                   donate_argnums=(0, 1))
def _fill_chunk(embs, mask, start, seed, chunk, topics, *doc_arrays,
                doc_len: int):
    key = jax.random.fold_in(jax.random.key(seed), chunk)
    e, m = _draw_docs(key, topics, *doc_arrays, doc_len=doc_len)
    return (jax.lax.dynamic_update_slice_in_dim(embs, e, start, 0),
            jax.lax.dynamic_update_slice_in_dim(mask, m, start, 0))


def make_device_corpus(
    *,
    n_docs: int,
    n_queries: int,
    seed: int = 0,
    doc_len: int = 128,
    min_doc_len: int = 32,
    query_len: int = 32,
    dim: int = 128,
    relevant_per_query: int = 4,
    distractors_per_query: int = 24,
    chunk_docs: int = 4096,
    sharding: Optional[jax.sharding.Sharding] = None,
) -> DeviceCorpus:
    """The :func:`make_retrieval_dataset` topic model, generated on the
    device in ``chunk_docs``-document chunks.

    Each device of ``sharding`` (default: the first device) allocates and
    draws exactly the doc rows it holds, into a buffer that is updated in
    place, and no row moves through the host or another device. The host
    only draws the small per-doc plan (topic, length, planting) and the
    queries. Relevant docs and distractors are disjoint across queries."""
    if sharding is None:
        sharding = SingleDeviceSharding(jax.devices()[0])
    rng = np.random.default_rng(seed)
    topics = _normalize(rng.standard_normal((N_TOPICS, dim))
                        .astype(np.float32))
    doc_topic = rng.integers(0, N_TOPICS, size=n_docs).astype(np.int32)
    doc_lens = rng.integers(min_doc_len, doc_len + 1,
                            size=n_docs).astype(np.int32)
    per_q = relevant_per_query + distractors_per_query
    if n_queries * per_q > n_docs:
        raise ValueError(f"{n_queries} queries x {per_q} planted docs "
                         f"exceed the {n_docs}-doc corpus")
    planted = rng.choice(n_docs, size=n_queries * per_q,
                         replace=False).reshape(n_queries, per_q)
    relevant = planted[:, :relevant_per_query]
    distractors = planted[:, relevant_per_query:]
    query_topic = rng.integers(0, N_TOPICS, size=n_queries).astype(np.int32)

    p_topic = np.full((n_docs,), -1, np.int32)
    p_strength = np.zeros((n_docs,), np.float32)
    p_noise = np.zeros((n_docs,), np.float32)
    p_count = np.zeros((n_docs,), np.float32)
    short = np.minimum(doc_lens, 16)
    for q in range(n_queries):
        rel, dis = relevant[q], distractors[q]
        p_topic[rel] = p_topic[dis] = query_topic[q]
        p_strength[rel], p_noise[rel] = TOPIC_STRENGTH, 0.3
        p_count[rel] = np.maximum(2, (TOPIC_STRENGTH * short[rel])
                                  .astype(np.int32))
        p_strength[dis], p_noise[dis] = DISTRACTOR_STRENGTH, 0.4
        p_count[dis] = np.maximum(
            1, (0.3 * np.minimum(doc_lens[dis], 12)).astype(np.int32))

    queries = np.zeros((n_queries, query_len, dim), np.float32)
    for q in range(n_queries):
        qn = rng.standard_normal((query_len, dim)).astype(np.float32)
        qmix = rng.uniform(0.15, 0.95, size=(query_len, 1)).astype(np.float32)
        qmix[rng.random(query_len) < 0.25] = 0.0
        queries[q] = _normalize(qmix * topics[query_topic[q]][None, :]
                                + (1 - qmix) * qn * 0.4)

    shape = (n_docs, doc_len, dim)
    if isinstance(sharding, NamedSharding):
        mask_sharding = NamedSharding(
            sharding.mesh, PartitionSpec(*tuple(sharding.spec)[:2]))
    else:
        mask_sharding = sharding
    doc_arrays = (doc_topic, doc_lens, p_topic, p_strength, p_noise, p_count)
    e_parts: List[jax.Array] = []
    m_parts: List[jax.Array] = []
    for dev, idx in sharding.addressable_devices_indices_map(shape).items():
        lo, hi, _ = idx[0].indices(n_docs)
        e = _zeros_on(dev, (hi - lo,) + shape[1:], jnp.bfloat16)
        m = _zeros_on(dev, (hi - lo, doc_len), jnp.bool_)
        t_dev = jax.device_put(topics, dev)
        for c0 in range(lo, hi, chunk_docs):
            c1 = min(c0 + chunk_docs, hi)
            part = [jax.device_put(a[c0:c1], dev) for a in doc_arrays]
            e, m = _fill_chunk(e, m, np.int32(c0 - lo), np.uint32(seed),
                               np.uint32(c0), t_dev, *part, doc_len=doc_len)
        e_parts.append(e)
        m_parts.append(m)
    embs = jax.make_array_from_single_device_arrays(shape, sharding, e_parts)
    mask = jax.make_array_from_single_device_arrays(shape[:2], mask_sharding,
                                                    m_parts)
    return DeviceCorpus(embs=embs, mask=mask, doc_lens=doc_lens,
                        doc_topic=doc_topic, topics=topics, queries=queries,
                        query_topic=query_topic, relevant=relevant,
                        distractors=distractors)
