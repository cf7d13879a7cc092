"""ISSUE 4: mesh-sharded corpus serving.

Every rerank flavor served from a corpus sharded over a real (virtual CPU)
mesh must return the identical top-K set as its single-device counterpart —
including on a RAGGED corpus whose tail shard owns fewer (or zero) docs.
Multi-device programs run in a subprocess with 4 host placeholder devices
(tests/_subproc.py), keeping the main pytest process single-device;
REPRO_KERNEL_IMPL is forwarded so CI's ref/interpret lanes reach the
shard_map paths.
"""
import numpy as np

from _subproc import run_in_subprocess

# Shared preamble: a ragged toy corpus (C=41 over 4 shards -> c_loc=11,
# valid=[11, 11, 11, 8]) + per-query candidate lists and their routed
# per-shard layouts on both a 4-shard mesh and the 1-device reference mesh.
_SETUP = """
import jax, jax.numpy as jnp, numpy as np
from repro.retrieval.service import (make_rerank_budgeted_step,
                                     make_rerank_dense_step,
                                     make_rerank_two_phase_step)
from repro.retrieval.sharded import (route_aligned, route_candidates,
                                     shard_corpus)

rng = np.random.default_rng(0)
C, L, M, B, T, N = 41, 12, 16, 4, 8, 16
emb = rng.standard_normal((C, L, M)).astype(np.float32)
emb /= np.linalg.norm(emb, axis=-1, keepdims=True)
msk = np.arange(L)[None] < rng.integers(4, L + 1, C)[:, None]
q_np = rng.standard_normal((B, T, M)).astype(np.float32)
q_np /= np.linalg.norm(q_np, axis=-1, keepdims=True)   # cells land in [-1, 1]
q = jnp.asarray(q_np)
cand = np.stack([rng.choice(C, N, replace=False)
                 for _ in range(B)]).astype(np.int32)

mesh4 = jax.make_mesh((2, 2), ("data", "model"))
mesh1 = jax.make_mesh((1,), ("data",))
sc = shard_corpus(emb, msk, mesh4)
assert (sc.n_shards, sc.docs_per_shard) == (4, 11)
assert list(sc.valid_docs) == [11, 11, 11, 8]
cand_l4 = route_candidates(cand, sc.docs_per_shard, sc.n_shards)
cand_l1 = cand[:, None, :]                    # 1 shard: slots == global ids
vd4 = sc.valid_docs


def check_topk(got_s, got_i, want_s, want_i, label):
    got_s, got_i = np.asarray(got_s), np.asarray(got_i)
    want_s, want_i = np.asarray(want_s), np.asarray(want_i)
    for b in range(got_i.shape[0]):
        assert set(got_i[b]) == set(want_i[b]), (label, b, got_i[b], want_i[b])
        np.testing.assert_allclose(np.sort(got_s[b]), np.sort(want_s[b]),
                                   atol=1e-4, err_msg=f"{label} q{b}")
"""


def test_dense_budgeted_two_phase_sharded_match_single_device():
    """Dense, full-budget budgeted, and exact-survivor two-phase steps on a
    4-shard ragged corpus reproduce the 1-device top-K exactly."""
    out = run_in_subprocess(_SETUP + """
# --- dense ---
d4 = make_rerank_dense_step(mesh4, topk=5, valid_docs=vd4)
d1 = make_rerank_dense_step(mesh1, topk=5)
s4, i4 = d4(sc.embs, sc.mask, q, jnp.asarray(cand_l4))
s1, i1 = d1(jnp.asarray(emb), jnp.asarray(msk), q, jnp.asarray(cand_l1))
check_topk(s4, i4, s1, i1, "dense")

# --- budgeted at full budget == dense ---
tok = np.broadcast_to(np.arange(T, dtype=np.int32)[None, None], (B, N, T))
tok_l4 = route_aligned(tok, cand, cand_l4, sc.docs_per_shard)
b4 = make_rerank_budgeted_step(mesh4, topk=5, tokens_per_doc=T,
                               valid_docs=vd4)
sb, ib = b4(sc.embs, sc.mask, q, jnp.asarray(cand_l4), jnp.asarray(tok_l4))
check_topk(sb, ib, s1, i1, "budgeted")

# --- two-phase with survivors == N_loc (phase 2 exact everywhere) ---
pooled = np.where(msk[:, :, None], emb, 0.0).mean(axis=1).astype(np.float32)
sc_p = shard_corpus(emb, msk, mesh4, pooled=pooled)
t4 = make_rerank_two_phase_step(mesh4, topk=5, survivors=N, valid_docs=vd4)
t1 = make_rerank_two_phase_step(mesh1, topk=5, survivors=N)
st4, it4 = t4(sc_p.embs, sc_p.mask, sc_p.pooled, q, jnp.asarray(cand_l4))
st1, it1 = t1(jnp.asarray(emb), jnp.asarray(msk), jnp.asarray(pooled), q,
              jnp.asarray(cand_l1))
check_topk(st4, it4, st1, it1, "two_phase")
print("FLAVORS_OK")
    """, n_devices=4)
    assert "FLAVORS_OK" in out


def test_sharded_pooled_bandit_matches_single_device():
    """Hard-bound mode (alpha_ef -> inf): the corpus-resident pooled-bandit
    shard_map flavor returns the identical top-K set as the single-device
    pooled engine AND the exact dense scores."""
    out = run_in_subprocess(_SETUP + """
from repro.retrieval.service import (make_rerank_bandit_step,
                                     rerank_bandit_step)

# valid per-cell support: normalized embeddings x normalized query tokens
a = jnp.full((B, N, T), -1.0, jnp.float32)
b = jnp.ones((B, N, T), jnp.float32)
a_l4 = route_aligned(np.asarray(a), cand, cand_l4, sc.docs_per_shard)
b_l4 = route_aligned(np.asarray(b), cand, cand_l4, sc.docs_per_shard)

step = make_rerank_bandit_step(mesh4, topk=5, alpha_ef=1e9, block_docs=4,
                               block_tokens=4, max_rounds=-1,
                               placement="corpus")
s4, i4, frac, stats = step(sc.embs, sc.mask, q, jnp.asarray(cand_l4),
                           jnp.asarray(a_l4), jnp.asarray(b_l4),
                           sc.valid_docs_device(), jnp.int32(0))
assert np.asarray(stats).shape == (4, 4)
assert (np.asarray(stats)[:, 3] == 0).all()   # clean corpus: no quarantine
assert ((np.asarray(frac) > 0) & (np.asarray(frac) <= 1)).all()

s1, i1, _, _ = rerank_bandit_step(
    jnp.asarray(emb), jnp.asarray(msk), q, jnp.asarray(cand), a, b,
    jax.random.key(0), topk=5, alpha_ef=1e9, block_docs=4, block_tokens=4)
check_topk(s4, i4, s1, i1, "bandit")

# dense exact reference, per query
d1 = make_rerank_dense_step(mesh1, topk=5)
sd, idd = d1(jnp.asarray(emb), jnp.asarray(msk), q, jnp.asarray(cand_l1))
check_topk(s4, i4, sd, idd, "bandit_vs_dense")
print("BANDIT_OK")
    """, n_devices=4)
    assert "BANDIT_OK" in out


def test_sharded_bandit_merges_exact_winner_scores():
    """At a finite alpha_ef each shard's bandit stops with partly revealed
    docs, whose score estimates are not comparable across shards; the
    merge must rank on exact MaxSim, so every returned score is the exact
    score of its doc and the list is sorted by it."""
    out = run_in_subprocess(_SETUP + """
from repro.retrieval.service import make_rerank_bandit_step

a = jnp.full((B, N, T), -1.0, jnp.float32)
b = jnp.ones((B, N, T), jnp.float32)
a_l4 = route_aligned(np.asarray(a), cand, cand_l4, sc.docs_per_shard)
b_l4 = route_aligned(np.asarray(b), cand, cand_l4, sc.docs_per_shard)
step = make_rerank_bandit_step(mesh4, topk=5, alpha_ef=0.3, block_docs=4,
                               block_tokens=4, max_rounds=-1,
                               placement="corpus")
s4, i4, frac, _ = step(sc.embs, sc.mask, q, jnp.asarray(cand_l4),
                       jnp.asarray(a_l4), jnp.asarray(b_l4),
                       sc.valid_docs_device(), jnp.int32(0))
s4, i4 = np.asarray(s4), np.asarray(i4)
assert ((np.asarray(frac) > 0) & (np.asarray(frac) <= 1)).all()
for qi in range(B):
    sims = np.einsum("nlm,tm->ntl", emb[i4[qi]], q_np[qi])
    exact = np.where(msk[i4[qi]][:, None, :], sims, -np.inf).max(-1).sum(-1)
    np.testing.assert_allclose(s4[qi], exact, rtol=1e-5, atol=1e-5)
    assert (np.diff(s4[qi]) <= 0).all()
print("EXACT_MERGE_OK")
    """, n_devices=4)
    assert "EXACT_MERGE_OK" in out


def test_exact_winners_counts_only_unrevealed_cells():
    """Rescoring a shard's winners exactly adds to the reveal count only
    the winner cells the bandit had not revealed: fully revealed winners
    add nothing, pad winners nothing, and the scores are exact."""
    import jax.numpy as jnp
    from repro.retrieval.service import _exact_winners

    rng = np.random.default_rng(0)
    C, L, M, T = 6, 4, 8, 3
    emb = rng.standard_normal((C, L, M)).astype(np.float32)
    q = rng.standard_normal((1, T, M)).astype(np.float32)
    mask = np.ones((C, L), bool)
    cand = np.array([[0, 2, 4, 5]], np.int32)
    bg = jnp.asarray([[14, 10, -1]], jnp.int32)        # rows 4, 0, a pad
    n_rev = jnp.asarray([7.0], jnp.float32)
    for won, fresh in (([T, T, T], 0), ([T, T, 0], 0), ([0, 1, 0], 2 * T - 1),
                       ([0, 0, 0], 2 * T)):
        s, got = _exact_winners(jnp.asarray(emb), jnp.asarray(mask),
                                jnp.asarray(q), jnp.asarray(cand),
                                jnp.asarray(cand + 10), bg, n_rev,
                                jnp.asarray([won], jnp.int32))
        assert float(got[0]) == 7.0 + fresh, (won, float(got[0]))
    exact = np.einsum("nlm,tm->ntl", emb[[4, 0]], q[0]).max(-1).sum(-1)
    np.testing.assert_allclose(np.asarray(s)[0, :2], exact, rtol=1e-5)
    assert float(s[0, 2]) < -1e30


def test_merge_scorecards_masks_pad_ids():
    """Regression (pad-id leakage): a shard with fewer than topk valid
    candidates ships -1-gid pad slots whose RAW scores (0.0 here) used to
    be gathered unmasked into the global top-K, beating genuinely negative
    real scores. One shard owns 0 candidates; all real scores are negative;
    the merge must still return only real ids, and -1 only for the
    shortfall beyond the number of real candidates."""
    out = run_in_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.retrieval.service import _merge_scorecards

mesh = jax.make_mesh((4,), ("x",))
B, NL, topk = 2, 3, 8
# shard s owns candidate gids {s*10 + j}; shard 3 owns NOTHING (all pads).
gids = np.full((B, 4, NL), -1, np.int32)
scores = np.zeros((B, 4, NL), np.float32)      # pads carry raw 0.0 scores
rng = np.random.default_rng(1)
for s in range(3):
    n_valid = [2, 3, 1][s]
    for j in range(n_valid):
        gids[:, s, j] = s * 10 + j
        scores[:, s, j] = -1.0 - rng.random((B,))   # all real scores < 0


def merged(sc, gd):
    return _merge_scorecards(sc[:, 0], gd[:, 0], ("x",), topk)


best, ids = jax.shard_map(
    merged, mesh=mesh, check_vma=False,
    in_specs=(P(None, "x", None), P(None, "x", None)),
    out_specs=(P(None, None), P(None, None)))(
        jnp.asarray(scores), jnp.asarray(gids))
best, ids = np.asarray(best), np.asarray(ids)
real = {0, 1, 10, 11, 12, 20}
for b in range(B):
    assert set(ids[b, :6]) == real, ids[b]          # no -1 pad beat a real
    assert (ids[b, 6:] == -1).all(), ids[b]         # genuine shortfall: -1
    assert (best[b, :6] < 0).all()                  # real (negative) scores
print("MERGE_OK")
    """, n_devices=4)
    assert "MERGE_OK" in out


def test_shard_global_ids_ragged_clamp_property():
    """Property over odd corpus sizes: with the ShardedCorpus valid_docs
    table, every genuine (shard, slot) maps to its unique global id —
    exactly a permutation of range(C) — and every padded-tail slot maps to
    -1 instead of aliasing a real doc."""
    out = run_in_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.retrieval.service import _shard_global_ids

mesh = jax.make_mesh((4,), ("x",))
for C in (5, 7, 13, 41, 42, 64):
    c_loc = -(-C // 4)
    valid = np.clip(C - c_loc * np.arange(4), 0, c_loc).astype(np.int32)
    slots = np.broadcast_to(np.arange(c_loc, dtype=np.int32),
                            (4, 1, c_loc)).copy()

    def gid_fn(cand, vd):
        return _shard_global_ids(cand, c_loc, ("x",), vd)

    gids = jax.shard_map(
        gid_fn, mesh=mesh, check_vma=False,
        in_specs=(P("x", None, None), P(None)),
        out_specs=P("x", None, None))(
            jnp.asarray(slots), jnp.asarray(valid))
    gids = np.asarray(gids).reshape(-1)
    kept = np.sort(gids[gids >= 0])
    assert kept.shape[0] == C, (C, kept)
    np.testing.assert_array_equal(kept, np.arange(C))   # no aliasing
    # unclamped legacy math WOULD alias: check the property is non-trivial
    if C % 4:
        n_pad = 4 * c_loc - C
        assert (gids == -1).sum() == n_pad
print("RAGGED_OK")
    """, n_devices=4)
    assert "RAGGED_OK" in out


def test_sharded_engine_zero_recompile_and_parity():
    """RetrievalEngine on a (2, 2) mesh: warmup pre-compiles every bucket,
    a mixed stream (provided + stage-1 candidates, both token buckets)
    serves with ZERO recompiles, per-shard metrics surface, and every
    completion's top-K matches the single-device engine bit-for-bit."""
    out = run_in_subprocess("""
import numpy as np
from repro.data.synthetic import make_retrieval_dataset
from repro.serve import EngineConfig, Request, RetrievalEngine

ds = make_retrieval_dataset(n_docs=47, n_queries=8, doc_len=16,
                            min_doc_len=6, query_len=16, dim=16, seed=3)
kw = dict(batch_size=4, deadline_s=0.5, token_buckets=(8, 16),
          cand_buckets=(16,), max_k=5, flavor="dense",
          stage1_candidates=16, stage1_kprime=4)
eng = RetrievalEngine(ds.doc_embs, ds.doc_mask,
                      EngineConfig(mesh_axes=(("data", 2), ("model", 2)),
                                   **kw))
solo = RetrievalEngine(ds.doc_embs, ds.doc_mask, EngineConfig(**kw))
assert eng.warmup() == solo.warmup()
rng = np.random.default_rng(0)
for i in range(8):
    n_tok = int(rng.integers(2, 17))
    cand = (rng.choice(47, int(rng.integers(5, 17)), replace=False)
            if i % 2 else None)
    for e in (eng, solo):
        e.submit(Request(query=ds.queries[i][:n_tok], k=5, cand_ids=cand))
got = {c.rid: c for c in eng.drain()}
want = {c.rid: c for c in solo.drain()}
assert len(got) == 8
for rid, c in got.items():
    assert set(c.topk_ids) == set(want[rid].topk_ids), rid
    np.testing.assert_allclose(np.sort(c.topk_scores),
                               np.sort(want[rid].topk_scores), atol=1e-4)
assert eng.metrics.compiles_after_warmup == 0
s = eng.metrics.summary()
assert s["n_shards"] == 4
assert len(s["shard_rounds_total"]) == 4
assert len(s["shard_occupancy_mean"]) == 4
print("ENGINE_OK")
    """, n_devices=4)
    assert "ENGINE_OK" in out


def test_sharded_engine_bandit_flavor_hard_bound_matches_dense():
    """Bandit flavor on the sharded engine (hard-bound mode): top-1 agrees
    with the sharded dense engine, reveal fraction lands in (0, 1], and the
    per-shard round counts show the frontier actually ran somewhere."""
    out = run_in_subprocess("""
import numpy as np
from repro.data.synthetic import make_retrieval_dataset
from repro.serve import EngineConfig, Request, RetrievalEngine

ds = make_retrieval_dataset(n_docs=47, n_queries=4, doc_len=16,
                            min_doc_len=6, query_len=8, dim=16, seed=3)
mesh = (("data", 2), ("model", 2))
kw = dict(batch_size=2, deadline_s=0.5, token_buckets=(8,),
          cand_buckets=(16,), max_k=5, stage1_candidates=16,
          stage1_kprime=4, mesh_axes=mesh)
bandit = RetrievalEngine(ds.doc_embs, ds.doc_mask,
                         EngineConfig(flavor="bandit", alpha_ef=1e9,
                                      block_docs=4, block_tokens=4, **kw))
dense = RetrievalEngine(ds.doc_embs, ds.doc_mask,
                        EngineConfig(flavor="dense", **kw))
cand = np.arange(16, dtype=np.int32)
for qi in (0, 1):
    q = ds.queries[qi][:8]
    bandit.submit(Request(query=q, k=5, cand_ids=cand))
    dense.submit(Request(query=q, k=5, cand_ids=cand))
got = {c.rid: c for c in bandit.drain()}
want = {c.rid: c for c in dense.drain()}
for rid, c in got.items():
    assert set(c.topk_ids) == set(want[rid].topk_ids), rid
    assert 0.0 < c.reveal_fraction <= 1.0
rec = bandit.metrics.batches[-1]
assert rec.shard_rounds is not None and sum(rec.shard_rounds) > 0
print("ENGINE_BANDIT_OK")
    """, n_devices=4)
    assert "ENGINE_BANDIT_OK" in out


def test_sharded_corpus_format_parity():
    """ISSUE 10: a quantized (int8) corpus sharded 4 ways returns the
    IDENTICAL top-K as the same-format 1-shard layout, for both serving
    flavors — the compressed payload decodes to the same f32 rows on
    every mesh, so sharding and quantization commute. Resident dtype and
    bytes are pinned too: the int8 corpus must ship as an s8 payload at
    >=3.5x less than the f32 dense bytes."""
    out = run_in_subprocess(_SETUP + """
from repro.kernels.quant import corpus_nbytes
from repro.retrieval.service import make_sharded_serving_step

a = np.full((B, N, T), -1.0, np.float32)      # valid unit-cosine support
bsup = np.ones((B, N, T), np.float32)
a_l4 = route_aligned(a, cand, cand_l4, sc.docs_per_shard)
b_l4 = route_aligned(bsup, cand, cand_l4, sc.docs_per_shard)
a_l1, b_l1 = a[:, None], bsup[:, None]
bf16_bytes = {}

for fmt in ("bf16", "int8"):
    sc4 = shard_corpus(emb, msk, mesh4, corpus_format=fmt)
    sc1 = shard_corpus(emb, msk, mesh1, corpus_format=fmt)
    bf16_bytes.setdefault(fmt, corpus_nbytes(sc4.embs))
    if fmt == "int8":
        assert str(sc4.embs.dtype) == str(sc1.embs.dtype) == "int8"
        # same padded doc count on both sides: 2x bf16 resident bytes is
        # the f32-dense equivalent the >=3.5x compression gate is against
        assert 2 * bf16_bytes["bf16"] / bf16_bytes["int8"] >= 3.5
    for flavor, kw in (("dense", {}),
                       ("bandit", dict(alpha_ef=1e9, block_docs=4,
                                       block_tokens=4, max_rounds=-1))):
        s4 = make_sharded_serving_step(mesh4, flavor, topk=5,
                                       corpus_format=fmt, **kw)
        s1 = make_sharded_serving_step(mesh1, flavor, topk=5,
                                       corpus_format=fmt, **kw)
        g4 = s4(sc4.embs, sc4.mask, q, jnp.asarray(cand_l4),
                jnp.asarray(a_l4), jnp.asarray(b_l4),
                sc4.valid_docs_device(), jnp.int32(0))
        g1 = s1(sc1.embs, sc1.mask, q, jnp.asarray(cand_l1),
                jnp.asarray(a_l1), jnp.asarray(b_l1),
                sc1.valid_docs_device(), jnp.int32(0))
        check_topk(g4[0], g4[1], g1[0], g1[1], f"{fmt}/{flavor}")

# cross-format fidelity: int8 dense scores track bf16 dense closely
d_bf = make_sharded_serving_step(mesh4, "dense", topk=5,
                                 corpus_format="bf16")
d_i8 = make_sharded_serving_step(mesh4, "dense", topk=5,
                                 corpus_format="int8")
sb = d_bf(shard_corpus(emb, msk, mesh4).embs, sc.mask, q,
          jnp.asarray(cand_l4), jnp.asarray(a_l4), jnp.asarray(b_l4),
          sc.valid_docs_device(), jnp.int32(0))
si = d_i8(shard_corpus(emb, msk, mesh4, corpus_format="int8").embs,
          sc.mask, q, jnp.asarray(cand_l4), jnp.asarray(a_l4),
          jnp.asarray(b_l4), sc.valid_docs_device(), jnp.int32(0))
np.testing.assert_allclose(np.sort(np.asarray(sb[0])),
                           np.sort(np.asarray(si[0])), atol=0.2)
print("FMT_PARITY_OK")
    """, n_devices=4)
    assert "FMT_PARITY_OK" in out
