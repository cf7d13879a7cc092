"""The resident index of one deployment, made on the device from the seed.

A copy of the program's topic-model generator
(``repro.data.synthetic.make_device_corpus``), kept here so that a change to
the program cannot change the benchmark's data; unlike the original, the
topic directions come from the configuration, not the seed. Every document
mixes its topic's direction with noise; the planted relevant documents and
distractors of each query carry tokens pulled hard towards the query's
topic. The host draws only the per-document plan (topic, length, planting)
and the queries; the token rows are drawn and written in place by one
jitted program, chunk by chunk, in the type they are served in (bf16).

A deployment over several chips holds its index as the engine shards it:
contiguous blocks of rows over a one-axis ``data`` mesh. Each chip then
runs the fill program for its own block, and no row crosses the host or
another chip; every chunk keeps the key of its global index, so the bytes
are those of the one-chip index at any chip count.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

N_TOPICS = 32
TOPIC_STRENGTH = 0.7         # planted relevant tokens' pull toward the topic
DISTRACTOR_STRENGTH = 0.55   # planted near-miss tokens' pull


@dataclasses.dataclass
class Corpus:
    embs: jax.Array            # (C, L, M) bf16, zero past each doc's length
    mask: jax.Array            # (C, L) bool
    doc_topic: np.ndarray      # (C,) int32
    queries: np.ndarray        # (Q, T, M) f32 unit rows
    query_topic: np.ndarray    # (Q,) int32
    planted: np.ndarray        # (Q, relevant + distractors) doc ids

    @property
    def n_docs(self) -> int:
        return self.embs.shape[0]


def _unit(x):
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-9)


def _normalize(x: np.ndarray) -> np.ndarray:
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-9)


def _draw_docs(key, topics, doc_topic, doc_lens, p_topic, p_strength,
               p_noise, p_count, *, doc_len: int):
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


def _fill_rows(key_seed, first_chunk, topics, plan, *, doc_len: int,
               dim: int, chunk: int):
    """The token rows of ``plan``'s documents in one program: a loop over
    chunks that draws each chunk (keyed by its global index, ``first_chunk``
    onwards) and writes it into the block in place."""
    n_docs = plan[0].shape[0]
    base = jax.random.key(key_seed)

    def body(i, carry):
        embs, mask = carry
        start = i * chunk
        part = [jax.lax.dynamic_slice_in_dim(a, start, chunk) for a in plan]
        e, m = _draw_docs(jax.random.fold_in(base, first_chunk + i), topics,
                          *part, doc_len=doc_len)
        return (jax.lax.dynamic_update_slice_in_dim(embs, e, start, 0),
                jax.lax.dynamic_update_slice_in_dim(mask, m, start, 0))

    init = (jnp.zeros((n_docs, doc_len, dim), jnp.bfloat16),
            jnp.zeros((n_docs, doc_len), jnp.bool_))
    return jax.lax.fori_loop(0, n_docs // chunk, body, init)


@functools.lru_cache(maxsize=None)
def _filler(device=None):
    """The fill program with its block placed on ``device`` (None: the
    default device)."""
    out = None if device is None else jax.sharding.SingleDeviceSharding(
        device)
    return jax.jit(_fill_rows, static_argnames=("doc_len", "dim", "chunk"),
                   out_shardings=out)


def index_mesh(cfg: dict, chips: int):
    """The mesh the engine builds for the configuration's ``mesh_axes``, or
    None for one chip. Refuses a configuration that does not name one
    ``data`` axis over exactly the cell's chips, or whose index does not
    split into whole chunks on each chip."""
    axes = [tuple(a) for a in cfg.get("engine", {}).get("mesh_axes", [])]
    want = [("data", chips)] if chips > 1 else []
    if axes != want:
        raise ValueError(f"engine.mesh_axes {axes} does not match the cell's "
                         f"{chips} chip(s): expected {want}")
    n_docs, chunk = cfg["corpus_docs"], cfg["corpus"]["chunk_docs"]
    if n_docs % (chips * chunk):
        raise ValueError(f"corpus_docs {n_docs} is not a multiple of "
                         f"{chips} chip(s) x chunk_docs {chunk}")
    if chips == 1:
        return None
    from repro.launch.mesh import make_mesh
    return make_mesh((chips,), ("data",))


def make_corpus(cfg: dict, seed: int, chips: int = 1) -> Corpus:
    """The index a configuration describes (``corpus_docs`` documents of
    ``min_doc_tokens``..``doc_tokens`` tokens of width ``dim``) and a pool
    of ``planted_queries`` queries of ``query_tokens`` tokens, from
    ``seed``; over ``chips`` chips, each chip's block of rows made on it."""
    mesh = index_mesh(cfg, chips)
    n_docs, doc_len, dim = (cfg["corpus_docs"], cfg["doc_tokens"],
                            cfg["dim"])
    gen = cfg["corpus"]
    n_q, chunk = gen["planted_queries"], gen["chunk_docs"]
    n_rel, n_dis = gen["relevant_per_query"], gen["distractors_per_query"]
    # The topic directions are part of the deployment (its configuration's
    # ``topic_seed``); the seed draws the documents and queries over them,
    # so that seeds change which data is asked about, not how hard it is.
    topics = _normalize(np.random.default_rng(gen["topic_seed"])
                        .standard_normal((N_TOPICS, dim)).astype(np.float32))
    rng = np.random.default_rng(seed)
    doc_topic = rng.integers(0, N_TOPICS, size=n_docs).astype(np.int32)
    doc_lens = rng.integers(cfg["min_doc_tokens"], doc_len + 1,
                            size=n_docs).astype(np.int32)
    per_q = n_rel + n_dis
    if n_q * per_q > n_docs:
        raise ValueError(f"{n_q} queries x {per_q} planted docs exceed the "
                         f"{n_docs}-doc corpus")
    planted = rng.choice(n_docs, size=n_q * per_q,
                         replace=False).reshape(n_q, per_q).astype(np.int32)
    query_topic = rng.integers(0, N_TOPICS, size=n_q).astype(np.int32)

    p_topic = np.full((n_docs,), -1, np.int32)
    p_strength = np.zeros((n_docs,), np.float32)
    p_noise = np.zeros((n_docs,), np.float32)
    p_count = np.zeros((n_docs,), np.float32)
    short = np.minimum(doc_lens, 16)
    rel, dis = planted[:, :n_rel], planted[:, n_rel:]
    p_topic[rel] = query_topic[:, None]
    p_topic[dis] = query_topic[:, None]
    p_strength[rel], p_noise[rel] = TOPIC_STRENGTH, 0.3
    p_count[rel] = np.maximum(2, (TOPIC_STRENGTH * short[rel])
                              .astype(np.int32))
    p_strength[dis], p_noise[dis] = DISTRACTOR_STRENGTH, 0.4
    p_count[dis] = np.maximum(
        1, (0.3 * np.minimum(doc_lens[dis], 12)).astype(np.int32))

    T = cfg["query_tokens"]
    qn = rng.standard_normal((n_q, T, dim)).astype(np.float32)
    qmix = rng.uniform(0.15, 0.95, size=(n_q, T, 1)).astype(np.float32)
    qmix[rng.random((n_q, T)) < 0.25] = 0.0
    queries = _normalize(qmix * topics[query_topic][:, None, :]
                         + (1 - qmix) * qn * 0.4)

    plan = (doc_topic, doc_lens, p_topic, p_strength, p_noise, p_count)
    key_seed = np.uint32(rng.integers(2**31))
    sizes = dict(doc_len=doc_len, dim=dim, chunk=chunk)
    if mesh is None:
        embs, mask = _filler()(key_seed, np.int32(0), jnp.asarray(topics),
                               tuple(jnp.asarray(a) for a in plan), **sizes)
    else:
        embs, mask = _fill_sharded(mesh, key_seed, topics, plan, sizes)
    return Corpus(embs=embs, mask=mask, doc_topic=doc_topic,
                  queries=queries, query_topic=query_topic, planted=planted)


def _fill_sharded(mesh, key_seed, topics, plan, sizes):
    """Each device of ``mesh`` fills the rows its ``data`` block holds;
    the blocks are assembled, without a copy, into the index placed as
    ``repro.retrieval.sharded.shard_corpus`` places it."""
    n_docs, chunk = plan[0].shape[0], sizes["chunk"]
    shape = (n_docs, sizes["doc_len"], sizes["dim"])
    e_sharding = NamedSharding(mesh, P("data", None, None))
    m_sharding = NamedSharding(mesh, P("data", None))
    e_parts, m_parts = [], []
    for dev, idx in e_sharding.addressable_devices_indices_map(shape).items():
        lo, hi, _ = idx[0].indices(n_docs)
        e, m = _filler(dev)(key_seed, np.int32(lo // chunk),
                            jax.device_put(topics, dev),
                            tuple(jax.device_put(a[lo:hi], dev)
                                  for a in plan), **sizes)
        e_parts.append(e)
        m_parts.append(m)
    return (jax.make_array_from_single_device_arrays(shape, e_sharding,
                                                     e_parts),
            jax.make_array_from_single_device_arrays(shape[:2], m_sharding,
                                                     m_parts))
