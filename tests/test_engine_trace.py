"""The serving engine's own tracing: per-batch stage stamps on
``BatchRecord``, the batch ordinal ``bid`` shared by a batch's record,
completions and profiler spans, the spans themselves (read back from a
CPU profiler trace), and the device name scopes in the compiled programs.

Threaded tests carry ``pytest.mark.timeout`` so a wedged serving thread
fails the run instead of hanging it.
"""
import gc
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.data.synthetic import make_retrieval_dataset
from repro.serve import (AsyncRetrievalEngine, EngineConfig, Request,
                         RetrievalEngine)

STAMPS = ("t_release", "t_prepared", "t_dispatched", "t_ready", "t_done",
          "t_delivered")


@pytest.fixture(scope="module")
def corpus():
    return make_retrieval_dataset(n_docs=32, n_queries=8, doc_len=12,
                                  min_doc_len=6, query_len=8, dim=16,
                                  seed=5)


def _cfg(**kw):
    # A 30 s admission deadline releases only full batches, so a request
    # stream submitted in order fixes every batch's composition.
    base = dict(batch_size=2, deadline_s=30.0, token_buckets=(8,),
                cand_buckets=(8,), max_k=5, flavor="bandit", max_rounds=2,
                block_docs=4, block_tokens=2, stage1_candidates=8,
                stage1_kprime=4, pipeline_depth=2)
    base.update(kw)
    return EngineConfig(**base)


def _requests(corpus, pattern):
    """One request per letter: ``c`` carries 8 candidates, ``s`` has
    none (the engine's stage-1 serves it)."""
    rng = np.random.default_rng(0)
    return [Request(query=corpus.queries[i % 8], k=5,
                    cand_ids=(rng.choice(32, 8, replace=False)
                              .astype(np.int32) if kind == "c" else None))
            for i, kind in enumerate(pattern)]


def _serve_async(corpus, pattern, **kw):
    eng = AsyncRetrievalEngine(corpus.doc_embs, corpus.doc_mask, _cfg(**kw))
    eng.warmup()
    eng.start()
    try:
        for r in _requests(corpus, pattern):
            eng.submit(r)
        comps = eng.drain()
    finally:
        eng.stop()
    return eng, comps


def _check_batches(eng, comps, n_batches):
    batches = eng.metrics.batches
    assert len(batches) == n_batches
    for b in batches:
        t = [getattr(b, s) for s in STAMPS]
        assert t == sorted(t), (b.bid, t)
        assert b.service_s == b.t_done - b.t_release
    assert sorted(b.bid for b in batches) == sorted({c.bid for c in comps})
    for b in batches:
        assert sum(c.bid == b.bid for c in comps) == b.n_real


def test_sync_engine_stamps_every_stage_in_order(corpus):
    eng = RetrievalEngine(corpus.doc_embs, corpus.doc_mask, _cfg())
    eng.warmup()
    for r in _requests(corpus, "ccss"):
        eng.submit(r)
    comps = eng.drain()
    _check_batches(eng, comps, 2)
    stage1 = [b.stage1_s for b in eng.metrics.batches]
    assert stage1[0] == 0.0 and stage1[1] > 0.0


@pytest.mark.timeout(120)
def test_async_engine_stamps_every_stage_in_order(corpus):
    eng, comps = _serve_async(corpus, "ccsscs")
    _check_batches(eng, comps, 3)
    # Stage-1 runs on the admit thread, inside the prepare stage.
    for b in eng.metrics.batches:
        assert b.stage1_s <= b.t_prepared - b.t_release


@pytest.mark.timeout(120)
def test_service_ema_reads_release_to_done(corpus):
    eng, _ = _serve_async(corpus, "cc")
    (b,) = eng.metrics.batches
    assert eng._admission_headroom() == b.t_done - b.t_release


# -- spans, read back from a CPU profiler trace ------------------------------

def _host_events(trace_dir):
    """(name, start_ns, end_ns, thread line, stats) of every host event."""
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                            (plane.name, i), dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def traced(corpus, tmp_path_factory):
    """One traced session: warm-up, then six requests (batches of two:
    candidates, stage-1, mixed) and one explicit collection while started."""
    d = str(tmp_path_factory.mktemp("engine-trace"))
    eng = AsyncRetrievalEngine(corpus.doc_embs, corpus.doc_mask, _cfg())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(d, profiler_options=opts):
        with TraceAnnotation("test.warmup"):
            eng.warmup()
        with TraceAnnotation("test.serve"):
            eng.start()
            try:
                for r in _requests(corpus, "ccsscs"):
                    eng.submit(r)
                eng.drain()
                gc.collect()
            finally:
                eng.stop()
    return eng, _host_events(d)


def _named(events, name):
    return [e for e in events if e[0] == name]


@pytest.mark.timeout(180)
def test_each_batch_writes_one_prepare_dispatch_and_harvest_span(traced):
    eng, events = traced
    bids = sorted(b.bid for b in eng.metrics.batches)
    assert len(bids) == 3
    for name in ("engine.prepare", "engine.dispatch", "engine.harvest",
                 "engine.harvest.wait", "engine.harvest.copy",
                 "engine.deliver"):
        assert sorted(e[4]["bid"] for e in _named(events, name)) == bids, \
            name


@pytest.mark.timeout(180)
def test_stage1_span_nests_in_prepare_for_candidate_less_batches(traced):
    eng, events = traced
    with_stage1 = sorted(b.bid for b in eng.metrics.batches
                         if b.stage1_s > 0)
    assert len(with_stage1) == 2             # "ss" and "cs", not "cc"
    stage1 = _named(events, "engine.stage1")
    assert sorted(e[4]["bid"] for e in stage1) == with_stage1
    prepare = {e[4]["bid"]: e for e in _named(events, "engine.prepare")}
    for _, lo, hi, line, stats in stage1:
        p = prepare[stats["bid"]]
        assert p[3] == line and p[1] <= lo and hi <= p[2]


@pytest.mark.timeout(180)
def test_compile_spans_only_during_warmup(traced):
    _, events = traced
    (warm,) = _named(events, "test.warmup")
    compiles = _named(events, "engine.compile")
    assert compiles
    assert all(warm[1] <= e[1] and e[2] <= warm[2] for e in compiles)


@pytest.mark.timeout(180)
def test_collection_while_started_writes_a_gc_span(traced):
    eng, events = traced
    (serve,) = _named(events, "test.serve")
    spans = [e for e in _named(events, "engine.gc")
             if serve[1] <= e[1] and e[2] <= serve[2]]
    assert spans and all("generation" in e[4] for e in spans)
    assert 2 in {e[4]["generation"] for e in spans}   # gc.collect()
    assert not any(cb == eng._gc_span for cb in gc.callbacks)


@pytest.mark.timeout(180)
def test_idle_spans_carry_no_batch(traced):
    _, events = traced
    for name in ("engine.admit.idle", "engine.dispatch.idle"):
        spans = _named(events, name)
        assert spans and not any("bid" in e[4] for e in spans), name


# -- device name scopes ---------------------------------------------------------

def test_compiled_programs_carry_the_device_scopes(corpus):
    eng = RetrievalEngine(corpus.doc_embs, corpus.doc_mask, _cfg())
    eng.warmup()
    step = eng._exec[("step", "bandit", 8, 8)].as_text()
    stage1 = eng._exec[("stage1", 8)].as_text()
    for scope in ("frontier_init", "frontier_round"):
        assert f"/{scope}/" in step, scope
    for scope in ("stage1_scan", "stage1_candidates"):
        assert f"/{scope}/" in stage1, scope
