"""Tracing of the four-chip routed path: the per-batch shard-health counter
``BatchRecord.shards_healthy``, the ``engine.shard_health`` span around
each health flip, the routed program's name scopes, and the routed
engine's answers against a plain per-shard f32 reference, healthy and with
one shard failed.

The mesh runs in a subprocess on 4 virtual CPU devices (``_subproc.py``).
The small index is stage-1-complete: ``stage1_kprime`` far above each
shard's token rows and ``stage1_candidates`` above its 12 documents, so
every shard's candidates are all of its documents and the right answer is
the exact top-k over the healthy shards' documents.
"""
import json

import numpy as np
import pytest

from _subproc import run_in_subprocess

_RUN = """
import glob, json, os, tempfile
import jax
import numpy as np
from jax.profiler import ProfileData
from repro.data.synthetic import make_retrieval_dataset
from repro.serve import EngineConfig, Request, RetrievalEngine

ds = make_retrieval_dataset(n_docs=48, n_queries=8, doc_len=12,
                            min_doc_len=6, query_len=8, dim=16, seed=7)
eng = RetrievalEngine(ds.doc_embs, ds.doc_mask, EngineConfig(
    batch_size=4, deadline_s=30.0, token_buckets=(8,), cand_buckets=(16,),
    max_k=5, flavor="bandit", alpha_ef=0.2, stage1="local",
    stage1_kprime=100_000, stage1_candidates=16,
    mesh_axes=(("data", 4),)))
eng.warmup()
out = {"hlo": eng._exec[("routed", "bandit", 8)].as_text()}


def serve(first, cand_ids=None):
    for i in range(first, first + 4):
        eng.submit(Request(query=ds.queries[i], k=5, cand_ids=cand_ids))
    comps = sorted(eng.drain(), key=lambda c: c.rid)
    return [[c.topk_ids.tolist(), c.topk_scores.tolist(), c.coverage]
            for c in comps]


trace_dir = tempfile.mkdtemp()
jax.profiler.start_trace(trace_dir)
out["healthy"] = serve(0)
eng.fail_shard(1)
out["failed"] = serve(4)
out["failed_host"] = serve(0, cand_ids=np.arange(0, 48, 3, dtype=np.int32))
eng.restore_shard(1)
out["restored"] = serve(4)
jax.profiler.stop_trace()
out["shards_healthy"] = [b.shards_healthy for b in eng.metrics.batches]
(path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                    recursive=True)
out["spans"] = [dict(e.stats) for p in ProfileData.from_file(path).planes
                if p.name.startswith("/host:CPU") for line in p.lines
                for e in line.events if e.name == "engine.shard_health"]
out["docs_per_shard"] = eng.corpus.docs_per_shard
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def routed():
    out = run_in_subprocess(_RUN, n_devices=4)
    return json.loads(out.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def dataset():
    from repro.data.synthetic import make_retrieval_dataset
    return make_retrieval_dataset(n_docs=48, n_queries=8, doc_len=12,
                                  min_doc_len=6, query_len=8, dim=16, seed=7)


def _reference(ds, queries, docs):
    """Exact f32 MaxSim top-5 of each query over ``docs``."""
    e = ds.doc_embs[docs].astype(np.float64)
    m = ds.doc_mask[docs]
    out = []
    for q in queries:
        sims = np.einsum("tm,nlm->ntl", q.astype(np.float64), e)
        sims = np.where(m[:, None, :], sims, -np.inf)
        s = sims.max(axis=-1).sum(axis=-1)
        top = np.argsort(-s, kind="stable")[:5]
        out.append((docs[top].tolist(), s[top]))
    return out


def test_batches_count_the_healthy_shards_they_were_prepared_with(routed):
    # Routed batches, then the host-routed path (candidate lists) while
    # the shard is down: each batch records the snapshot it read.
    assert routed["shards_healthy"] == [4, 3, 3, 4]


def test_off_mesh_batches_carry_no_shard_count(dataset):
    from repro.serve import EngineConfig, Request, RetrievalEngine
    eng = RetrievalEngine(dataset.doc_embs, dataset.doc_mask, EngineConfig(
        batch_size=2, deadline_s=30.0, token_buckets=(8,),
        cand_buckets=(16,), max_k=5, flavor="dense", stage1_candidates=16))
    eng.warmup()
    for i in range(2):
        eng.submit(Request(query=dataset.queries[i], k=5))
    eng.drain()
    assert [b.shards_healthy for b in eng.metrics.batches] == [None]


def test_each_health_flip_is_a_span_with_its_shard(routed):
    # The profiler records a bool as 0 or 1.
    spans = [(int(s["shard"]), int(s["healthy"])) for s in routed["spans"]]
    assert spans == [(1, 0), (1, 1)]


def test_routed_program_carries_the_merge_scopes(routed):
    for scope in ("routed_stage1", "routed_rerank", "routed_merge"):
        assert f"/{scope}/" in routed["hlo"], scope
    # The merge's collectives are named by its scope.
    gathers = [line for line in routed["hlo"].splitlines()
               if " all-gather" in line or " all-reduce" in line]
    assert gathers and all("/routed_merge/" in g for g in gathers)


@pytest.mark.parametrize("phase, down", [("healthy", None),
                                         ("failed", 1), ("restored", None)])
def test_answers_equal_the_per_shard_reference(routed, dataset, phase,
                                               down):
    per = routed["docs_per_shard"]
    docs = np.array([d for d in range(48)
                     if down is None or d // per != down])
    first = 0 if phase == "healthy" else 4
    want = _reference(dataset, dataset.queries[first:first + 4], docs)
    for (ids, scores, coverage), (ref_ids, ref_scores) in zip(
            routed[phase], want):
        assert sorted(ids) == sorted(ref_ids)
        np.testing.assert_allclose(sorted(scores), sorted(ref_scores),
                                   rtol=1e-5, atol=1e-5)
        assert coverage == (1.0 if down is None else 0.75)
