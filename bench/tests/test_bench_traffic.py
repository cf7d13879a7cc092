"""Seeded traffic repeats exactly; every seed offers the same load."""
import numpy as np

from bench.corpus import make_corpus
from bench.requests import make_templates, size_grid
from bench_cells import BENCH, load
import importlib.util
import os


def _poisson():
    path = os.path.join(BENCH, "traffic", "poisson.py")
    spec = importlib.util.spec_from_file_location("poisson_kind", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


SMALL = {"query_tokens": 8, "doc_tokens": 16, "min_doc_tokens": 8,
         "dim": 32, "corpus_docs": 256,
         "corpus": {"topic_seed": 0, "chunk_docs": 64, "planted_queries": 8,
                    "relevant_per_query": 4, "distractors_per_query": 24}}


def test_schedule_repeats_and_keeps_its_gaps_across_seeds():
    sched = _poisson().schedule
    big = 2**31 + 12345
    a = sched(700.0, 2.0, np.random.default_rng(big))
    b = sched(700.0, 2.0, np.random.default_rng(big))
    c = sched(700.0, 2.0, np.random.default_rng(7))
    assert np.array_equal(a, b)
    assert len(a) == 1400 and a[0] == 0.0
    assert not np.array_equal(a, c)
    np.testing.assert_allclose(np.sort(np.diff(a)), np.sort(np.diff(a)))
    assert abs(a[-1] - c[-1]) < 0.05            # same total, other order


def test_templates_repeat_exactly_and_share_sizes_across_seeds():
    mix = {"templates": 24, "candidates": [16, 48]}

    def draw(seed):
        corpus = make_corpus(SMALL, seed)
        return corpus, make_templates(corpus, mix, np.random.default_rng(seed))

    c1, t1 = draw(11)
    c2, t2 = draw(11)
    _, t3 = draw(12)
    assert np.array_equal(np.asarray(c1.embs), np.asarray(c2.embs))
    for a, b in zip(t1, t2):
        assert np.array_equal(a.query, b.query)
        assert np.array_equal(a.cand_ids, b.cand_ids)
    sizes = sorted(len(t.cand_ids) for t in t1)
    assert sizes == sorted(size_grid(16, 48, 24).tolist())
    assert sizes == sorted(len(t.cand_ids) for t in t3)
    for t in t1:
        assert len(set(t.cand_ids.tolist())) == len(t.cand_ids)
        assert t.cand_ids.min() >= 0 and t.cand_ids.max() < 256


def test_stage1_mix_carries_no_candidates():
    mix = load(os.path.join(BENCH, "traffic", "text-stage1-backlog.json"))
    mix["templates"] = 8
    corpus = make_corpus(SMALL, 1)
    ts = make_templates(corpus, mix, np.random.default_rng(1))
    assert all(t.cand_ids is None for t in ts)
