"""A mix's ``topic_zipf``: without it the templates are drawn as before
skew existed; with it each topic is asked about in its Zipf share."""
import numpy as np
import pytest

from bench.corpus import N_TOPICS, make_corpus
from bench.requests import candidate_list, make_templates, query_weights, \
    size_grid

SMALL = {"query_tokens": 8, "doc_tokens": 16, "min_doc_tokens": 8,
         "dim": 32, "corpus_docs": 2048,
         "corpus": {"topic_seed": 0, "chunk_docs": 64, "planted_queries": 64,
                    "relevant_per_query": 4, "distractors_per_query": 24}}
SEED = 2**31 + 21


@pytest.fixture(scope="module")
def corpus():
    return make_corpus(SMALL, SEED)


def _unskewed(corpus, mix, rng):
    """The draws of the templates as they were made before ``topic_zipf``:
    the same rng calls in the same order."""
    n, n_q = mix["templates"], corpus.queries.shape[0]
    qs = rng.permutation(np.arange(n) % n_q)
    if mix.get("candidates") is None:
        return [(corpus.queries[q], None) for q in qs]
    by_topic = {t: np.flatnonzero(corpus.doc_topic == t)
                for t in np.unique(corpus.query_topic)}
    sizes = rng.permutation(size_grid(*mix["candidates"], n))
    return [(corpus.queries[q], candidate_list(corpus, q, int(s), by_topic,
                                               rng))
            for q, s in zip(qs, sizes)]


@pytest.mark.parametrize("candidates", [None, [16, 48]])
@pytest.mark.parametrize("zipf", [None, 0])
def test_without_skew_the_templates_are_unchanged(corpus, candidates, zipf):
    mix = {"templates": 96, "candidates": candidates}
    if zipf is not None:
        mix["topic_zipf"] = zipf
    got = make_templates(corpus, mix, np.random.default_rng(SEED))
    want = _unskewed(corpus, mix, np.random.default_rng(SEED))
    assert len(got) == len(want)
    for t, (q, c) in zip(got, want):
        assert np.array_equal(t.query, q)
        assert (t.cand_ids is None and c is None) or np.array_equal(
            t.cand_ids, c)


def test_hottest_topic_takes_its_zipf_share(corpus):
    mix = {"templates": 8192, "candidates": None, "topic_zipf": 1.0}
    templates = make_templates(corpus, mix, np.random.default_rng(SEED))
    # The topic ranks are the run's first draw.
    rank = 1 + np.random.default_rng(SEED).permutation(N_TOPICS)
    present = np.unique(corpus.query_topic)
    hot = present[np.argmin(rank[present])]
    want = 1.0 / rank[hot] / np.sum(1.0 / rank[present])
    of_query = {corpus.queries[q].tobytes(): corpus.query_topic[q]
                for q in range(corpus.queries.shape[0])}
    topics = np.asarray([of_query[t.query.tobytes()] for t in templates])
    share = np.mean(topics == hot)
    assert want > 2.0 / len(present)          # a real skew
    assert abs(share - want) < 0.03, (share, want)
    # Within a topic, its planted queries are asked about alike.
    weights = query_weights(corpus.query_topic, 1.0,
                            np.random.default_rng(SEED))
    assert np.isclose(weights.sum(), 1.0)
    for t in present:
        w = weights[corpus.query_topic == t]
        assert np.allclose(w, w[0])
