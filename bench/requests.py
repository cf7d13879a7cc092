"""Request templates: what one request of a traffic mix asks.

A run draws ``templates`` distinct requests and sends them in a seeded
order; the traffic kind decides when. Every seed sees the same multiset of
candidate-list sizes (an even grid over the mix's range), so seeds change
which documents and queries are asked about, not how much work is asked.

Without ``topic_zipf`` every planted query is asked about equally often.
With ``topic_zipf: s`` the topics are ranked in a seeded order and each
template asks about topic ranked ``r`` with probability proportional to
``r ** -s`` (the topics that hold planted queries), then about one of its
planted queries, each alike: hot topics are asked about again and again.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

from bench.corpus import N_TOPICS, Corpus


@dataclasses.dataclass(frozen=True)
class Template:
    query: np.ndarray                 # (T, M) f32
    cand_ids: Optional[np.ndarray]    # (n,) int32 doc ids; None = stage-1


def size_grid(lo: int, hi: int, n: int) -> np.ndarray:
    """``n`` sizes spread evenly over [lo, hi]."""
    return np.rint(lo + (hi - lo) * (np.arange(n) + 0.5) / n).astype(int)


def candidate_list(corpus: Corpus, q: int, n: int, by_topic,
                   rng: np.random.Generator) -> np.ndarray:
    """The query's planted docs, then docs of its topic (half the list),
    then random docs, deduplicated, in a seeded order."""
    topical = by_topic[corpus.query_topic[q]]
    ids = np.concatenate([corpus.planted[q],
                          rng.choice(topical, size=min(len(topical), n // 2),
                                     replace=False)])
    _, first = np.unique(ids, return_index=True)
    ids = ids[np.sort(first)][:n]
    while len(ids) < n:
        extra = rng.integers(corpus.n_docs, size=2 * (n - len(ids)))
        ids = np.concatenate([ids, extra])
        _, first = np.unique(ids, return_index=True)
        ids = ids[np.sort(first)][:n]
    return rng.permutation(ids.astype(np.int32))


def query_weights(query_topic: np.ndarray, s: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Probability of each planted query under a Zipf law of exponent
    ``s`` over the topics, ranked in a seeded order."""
    rank = 1 + rng.permutation(N_TOPICS)
    topics, count = np.unique(query_topic, return_counts=True)
    share = np.zeros(N_TOPICS)
    share[topics] = rank[topics] ** -float(s)
    share /= share.sum()
    per_query = np.zeros(N_TOPICS)
    per_query[topics] = share[topics] / count
    return per_query[query_topic]


def make_templates(corpus: Corpus, mix: dict,
                   rng: np.random.Generator) -> List[Template]:
    n = mix["templates"]
    n_q = corpus.queries.shape[0]
    if mix.get("topic_zipf"):
        qs = rng.choice(n_q, size=n,
                        p=query_weights(corpus.query_topic,
                                        mix["topic_zipf"], rng))
    else:
        qs = rng.permutation(np.arange(n) % n_q)
    cand = mix.get("candidates")
    if cand is None:
        return [Template(corpus.queries[q], None) for q in qs]
    by_topic = {t: np.flatnonzero(corpus.doc_topic == t)
                for t in np.unique(corpus.query_topic)}
    sizes = rng.permutation(size_grid(cand[0], cand[1], n))
    return [Template(corpus.queries[q],
                     candidate_list(corpus, q, int(s), by_topic, rng))
            for q, s in zip(qs, sizes)]
