"""Stage-1 kNN candidate generation (Eq. 15 bounds) + two-stage pipeline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import BanditConfig
from repro.data.synthetic import make_retrieval_dataset
from repro.kernels import ref as kref
from repro.retrieval.ann import generate_candidates
from repro.retrieval.index import build_index, build_index_from_ragged
from repro.retrieval.pipeline import evaluate_dataset, rerank_query


@pytest.fixture(scope="module")
def ds():
    return make_retrieval_dataset(n_docs=128, n_queries=4, seed=0)


@pytest.fixture(scope="module")
def index(ds):
    return build_index(ds.doc_embs, ds.doc_mask, ds.doc_lens)


def test_ann_bounds_are_valid_upper_bounds(ds, index):
    """THE paper-critical property (Eq. 15): b_it >= H_it for every
    candidate cell — otherwise the hard bounds (and hence Col-Bandit's
    stopping certificate) would be wrong."""
    for qi in range(ds.n_queries):
        q = jnp.asarray(ds.queries[qi])
        cand = generate_candidates(index.doc_embs, index.doc_mask, q,
                                   kprime=10, max_candidates=64)
        embs, mask = index.gather_docs(cand.doc_ids)
        h = kref.maxsim_ref(embs, mask, q)
        h = jnp.where(cand.doc_mask[:, None], h, 0.0)
        viol = np.asarray(h - cand.b)
        assert viol.max() <= 1e-5, f"bound violated by {viol.max()}"


@pytest.mark.parametrize("chunk_docs", [1, 5, 32, 100])
def test_chunked_stage1_scan_matches_whole_index(ds, index, chunk_docs):
    """The document-chunked running top-k' scan returns the same
    CandidateSet as one top_k over the whole (T, C*L) similarity matrix
    (chunk_docs >= C), ragged last chunk included: identical ids and masks,
    and values equal to f32 rounding (the CPU dot's summation order
    depends on the operand's width)."""
    C = index.doc_embs.shape[0]
    for qi in range(ds.n_queries):
        q = jnp.asarray(ds.queries[qi])
        for quota in (None, jnp.int32(20)):
            whole = generate_candidates(index.doc_embs, index.doc_mask, q,
                                        quota, kprime=10, max_candidates=64,
                                        chunk_docs=C)
            got = generate_candidates(index.doc_embs, index.doc_mask, q,
                                      quota, kprime=10, max_candidates=64,
                                      chunk_docs=chunk_docs)
            for a, b in zip(got, whole):
                a, b = np.asarray(a), np.asarray(b)
                if a.dtype == np.float32:
                    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
                else:
                    np.testing.assert_array_equal(a, b)


def test_ann_known_cells_match_truth(ds, index):
    q = jnp.asarray(ds.queries[0])
    cand = generate_candidates(index.doc_embs, index.doc_mask, q,
                               kprime=10, max_candidates=64)
    embs, mask = index.gather_docs(cand.doc_ids)
    h = np.asarray(kref.maxsim_ref(embs, mask, q))
    km = np.asarray(cand.known_mask)
    kv = np.asarray(cand.known_vals)
    assert km.any()
    np.testing.assert_allclose(kv[km], h[km], atol=1e-5)


def test_candidates_cover_per_token_winners(ds, index):
    """Guaranteed stage-1 property: the doc owning the single best token for
    EACH query token is in the candidate set (it is that token's top-1
    neighbor). The global sum-winner is NOT guaranteed — two-stage retrieval
    accepts stage-1 recall loss, exactly as in the paper's pipeline."""
    for qi in range(ds.n_queries):
        q = jnp.asarray(ds.queries[qi])
        h_all = kref.maxsim_ref(index.doc_embs, index.doc_mask, q)
        cand = generate_candidates(index.doc_embs, index.doc_mask, q,
                                   kprime=10, max_candidates=64)
        ids = set(np.asarray(cand.doc_ids).tolist())
        for t in range(0, q.shape[0], 7):        # spot-check tokens
            owner = int(jnp.argmax(h_all[:, t]))
            assert owner in ids


def test_pipeline_exact_is_reference(index, ds):
    r = rerank_query(index, jnp.asarray(ds.queries[0]), method="exact", k=5)
    assert r.overlap == 1.0 and r.coverage == 1.0


@pytest.mark.parametrize("method", ["bandit", "batched", "uniform",
                                    "topmargin"])
def test_pipeline_methods_run(index, ds, method):
    r = rerank_query(index, jnp.asarray(ds.queries[1]), method=method, k=5,
                     bandit=BanditConfig(k=5, alpha_ef=0.5),
                     qrels_row=ds.qrels[1])
    assert 0.0 < r.coverage <= 1.0
    assert 0.0 <= r.overlap <= 1.0
    assert r.flops <= r.flops_exact + 1e-6
    assert set(r.metrics) == {"recall", "mrr", "ndcg"}


def test_bandit_beats_uniform_at_matched_coverage(ds):
    """Qualitative claim of the paper (Fig. 2): at matched coverage the
    adaptive method achieves higher overlap than Doc-Uniform."""
    out_b = evaluate_dataset(ds, method="bandit", k=5,
                             bandit=BanditConfig(k=5, alpha_ef=1.0))
    out_u = evaluate_dataset(ds, method="uniform", k=5,
                             budget_fraction=max(0.05, out_b["coverage"]))
    assert out_b["overlap"] >= out_u["overlap"] - 0.05


def test_prereveal_ann_reduces_paid_coverage(index, ds):
    base = rerank_query(index, jnp.asarray(ds.queries[2]), method="bandit",
                        k=5, bandit=BanditConfig(k=5, alpha_ef=0.5))
    pre = rerank_query(index, jnp.asarray(ds.queries[2]), method="bandit",
                       k=5, bandit=BanditConfig(k=5, alpha_ef=0.5),
                       prereveal_ann=True)
    assert pre.flops <= base.flops * 1.05


def test_ragged_index_building():
    rng = np.random.default_rng(0)
    docs = [rng.standard_normal((l, 8)).astype(np.float32)
            for l in (3, 7, 5)]
    idx = build_index_from_ragged(docs)
    assert idx.doc_embs.shape == (3, 7, 8)
    assert np.asarray(idx.doc_lens).tolist() == [3, 7, 5]
    assert np.asarray(idx.doc_mask).sum() == 15
