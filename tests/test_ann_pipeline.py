"""Stage-1 kNN candidate generation (Eq. 15 bounds) + two-stage pipeline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import BanditConfig
from repro.data.synthetic import make_retrieval_dataset
from repro.kernels import ref as kref
from repro.retrieval.ann import (generate_candidates,
                                 generate_candidates_batch, token_topk)
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
    # The batched entry point scans every query's tokens as rows of one
    # scan and yields each query's set exactly as the per-query call does.
    qs = jnp.asarray(ds.queries[:ds.n_queries])
    batch = generate_candidates_batch(index.doc_embs, index.doc_mask, qs,
                                      kprime=10, max_candidates=64,
                                      chunk_docs=chunk_docs)
    one = jax.vmap(lambda q: generate_candidates(
        index.doc_embs, index.doc_mask, q, kprime=10, max_candidates=64,
        chunk_docs=chunk_docs))(qs)
    for a, b in zip(batch, one):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _tied_index(C, L, M, B, T, seed):
    """Small integer-valued bf16 index and f32 queries, so every dot is
    exact in f32 and the chunked scan must match one top_k bit for bit.
    Every third document repeats the one before it (tied similarities
    across documents), documents 2 and C-1 are fully masked, and the rest
    have ragged lengths."""
    rng = np.random.default_rng(seed)
    embs = rng.integers(-2, 3, (C, L, M)).astype(np.float32)
    embs[3::3] = embs[2:-1:3]
    mask = np.arange(L)[None] < rng.integers(1, L + 1, C)[:, None]
    mask[3::3] = mask[2:-1:3]
    mask[2] = mask[C - 1] = False
    q = rng.integers(-2, 3, (B, T, M)).astype(np.float32)
    return (jnp.asarray(embs, jnp.bfloat16), jnp.asarray(mask),
            jnp.asarray(q))


# (C docs, L tokens, chunk_docs, k'): a ragged last chunk, chunks of one
# document and wider than the index, and a k' above a chunk's tokens. The
# last four pre-select documents in every full chunk (more than k'
# documents, L > k'); the ragged tails of three documents do not.
TIED_CASES = [(13, 4, 5, 6), (13, 4, 1, 3), (13, 4, 64, 6), (11, 3, 1, 10),
              (10, 4, 4, 7), (13, 8, 5, 3), (21, 6, 6, 3), (16, 5, 8, 2),
              (9, 16, 9, 4)]


@pytest.mark.parametrize("C,L,chunk_docs,kprime", TIED_CASES)
def test_token_topk_rows_exact(C, L, chunk_docs, kprime):
    """token_topk over a batch's flattened (B*T, M) query-token rows equals,
    bit for bit, the per-query vmapped scan and one lax.top_k over each
    query's whole (T, C*L) similarity matrix: values, doc ids, and the
    lower-position tie-break across tied documents."""
    B, T, M = 3, 5, 8
    embs, mask, q = _tied_index(C, L, M, B, T, seed=C * 100 + chunk_docs)
    vals, docs = token_topk(embs, mask, q, kprime, chunk_docs)
    assert vals.shape == docs.shape == (B, T, kprime)

    per_query = jax.vmap(
        lambda qq: token_topk(embs, mask, qq, kprime, chunk_docs))(q)
    sims = jnp.einsum("btm,nm->btn", q,
                      embs.reshape(C * L, M).astype(jnp.float32))
    sims = jnp.where(mask.reshape(-1), sims, jnp.float32(-3e38))
    w_vals, w_pos = jax.lax.top_k(sims, kprime)
    for want_vals, want_docs in (per_query, (w_vals, w_pos // L)):
        np.testing.assert_array_equal(np.asarray(vals), np.asarray(want_vals))
        np.testing.assert_array_equal(np.asarray(docs), np.asarray(want_docs))
    # The planted ties are selected: some token's list holds a document and
    # its copy, side by side with equal values, the lower id first.
    d, v = np.asarray(docs), np.asarray(vals)
    assert ((d[..., 1:] == d[..., :-1] + 1) & (d[..., :-1] % 3 == 2)
            & (v[..., 1:] == v[..., :-1])).any()


@pytest.mark.parametrize("C,L,chunk_docs,kprime", TIED_CASES)
def test_generate_candidates_batch_matches_per_query(C, L, chunk_docs,
                                                     kprime):
    """generate_candidates_batch equals the vmapped per-query
    generate_candidates field by field, with and without quotas."""
    B, T, M = 3, 5, 8
    embs, mask, q = _tied_index(C, L, M, B, T, seed=C * 100 + chunk_docs)
    kw = dict(kprime=kprime, max_candidates=8, chunk_docs=chunk_docs)
    for quotas in (None, jnp.asarray([2, 8, 5], jnp.int32)):
        got = generate_candidates_batch(embs, mask, q, quotas, **kw)
        if quotas is None:
            want = jax.vmap(lambda qq: generate_candidates(
                embs, mask, qq, **kw))(q)
        else:
            want = jax.vmap(lambda qq, n: generate_candidates(
                embs, mask, qq, n, **kw))(q, quotas)
        for field, a, b in zip(got._fields, got, want):
            assert a.shape[0] == B, field
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=field)


def _planted_index(C, L, chunk, kprime):
    """A (C, L, M) index whose similarities with the query row t are
    embs[:, :, t] (the queries are the unit vectors), so each row's scores
    are set by hand over small integer noise: row 0 has one document (8)
    holding all of its top-k' tokens; row 1 has k'-1 documents with one
    token each above 20 and two documents (13 < 14) whose maxima tie at 20
    on the k'-th document, where 14's other tokens beat 13's; the third
    chunk's documents are all masked, and its noise is 99 in every row."""
    T = M = 8
    rng = np.random.default_rng(C * 10 + chunk)
    embs = rng.integers(-8, 9, (C, L, M)).astype(np.float32)
    embs[8, :kprime, 0] = 40 - np.arange(kprime)
    embs[[9, 11, 12][:kprime - 1], 0, 1] = [30, 29, 28][:kprime - 1]
    embs[13, L - 1, 1] = embs[14, 0, 1] = 20
    embs[14, 1:, 1] = 15
    embs[2 * chunk:3 * chunk] = 99
    mask = np.arange(L)[None] < rng.integers(1, L + 1, C)[:, None]
    mask[[8, 13, 14]] = True
    mask[2 * chunk:3 * chunk] = False
    q = np.eye(M, dtype=np.float32).reshape(2, T // 2, M)
    return (jnp.asarray(embs, jnp.bfloat16), jnp.asarray(mask),
            jnp.asarray(q))


@pytest.mark.parametrize("chunk_docs", [8, 5, 4, 35])
def test_token_topk_preselection_planted(chunk_docs):
    """Per-document pre-selection keeps exactly one top_k over the whole
    (T, C*L) matrix where it could go wrong: one document holding all of a
    row's top-k' tokens, two documents tied at the k'-th document's max
    (the lower id wins although the other's next tokens are higher), a
    chunk of masked documents, and chunks of at most k' documents (4)."""
    C, L, kprime = 35, 6, 4
    embs, mask, q = _planted_index(C, L, 8, kprime)
    vals, docs = token_topk(embs, mask, q, kprime, chunk_docs)
    sims = jnp.einsum("btm,nm->btn", q,
                      embs.reshape(C * L, 8).astype(jnp.float32))
    sims = jnp.where(mask.reshape(-1), sims, jnp.float32(-3e38))
    w_vals, w_pos = jax.lax.top_k(sims, kprime)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(w_vals))
    np.testing.assert_array_equal(np.asarray(docs), np.asarray(w_pos // L))
    np.testing.assert_array_equal(np.asarray(docs[0, 0]), [8] * kprime)
    np.testing.assert_array_equal(np.asarray(vals[0, 1]), [30, 29, 28, 20])
    np.testing.assert_array_equal(np.asarray(docs[0, 1]), [9, 11, 12, 13])
    assert not ((docs >= 16) & (docs < 24)).any()
    kw = dict(kprime=kprime, max_candidates=8, chunk_docs=chunk_docs)
    got = generate_candidates_batch(embs, mask, q, **kw)
    want = jax.vmap(lambda qq: generate_candidates(embs, mask, qq, **kw))(q)
    for field, a, b in zip(got._fields, got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=field)


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
