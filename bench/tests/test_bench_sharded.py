"""A sharded deployment on 4 CPU devices: the index made on each device
equals the one-device index, the reference reads it where it lies, and a
routed twin (shard-local stage-1, one shard failed for part of the window)
runs by name and is judged, with planted faults that must fail."""
import pytest

from four_devices import run_on_four


@pytest.fixture(scope="module")
def index():
    return run_on_four("""
        import json
        import numpy as np
        import jax
        from bench import harness, reference
        from bench.corpus import make_corpus
        from bench_cells import BENCH, load
        from repro.serve.engine import RetrievalEngine

        cfg = load(f"{BENCH}/configs/colbert-text.json")
        cfg.update(query_tokens=8, doc_tokens=16, min_doc_tokens=8, dim=32,
                   corpus_docs=1024)
        cfg["engine"].update(token_buckets=[8], cand_buckets=[32],
                             stage1_candidates=32, stage1_kprime=4)
        cfg["corpus"].update(chunk_docs=64, planted_queries=8)
        one = make_corpus(cfg, 2**31 + 9)
        cfg["engine"].update(mesh_axes=[["data", 4]], stage1="local")
        four = make_corpus(cfg, 2**31 + 9, chips=4)
        out = {
            "embs_equal": np.array_equal(
                np.asarray(one.embs).view(np.uint16),
                np.asarray(four.embs).view(np.uint16)),
            "mask_equal": np.array_equal(np.asarray(one.mask),
                                         np.asarray(four.mask)),
            "blocks": [[s.index[0].start, s.index[0].stop, s.device.id]
                       for s in four.embs.addressable_shards],
            "mask_blocks": [[s.index[0].start, s.device.id]
                            for s in four.mask.addressable_shards]}
        with jax.transfer_guard_device_to_device("disallow"):
            eng = RetrievalEngine(four.embs, four.mask,
                                  harness.engine_config(cfg))
        out["engine_holds_it"] = eng.corpus_embs is four.embs
        rng = np.random.default_rng(4)
        qs = [one.queries[i] for i in range(6)]
        cands = [np.sort(rng.choice(1024, size=n, replace=False))
                 .astype(np.int32) for n in (5, 40, 100, 256, 300, 64)]
        out["scores_equal"] = (reference.maxsim_scores(
            one.embs, one.mask, qs, cands) == reference.maxsim_scores(
            four.embs, four.mask, qs, cands))
        kw = dict(kprime=4, max_candidates=32, span=128)
        whole = [reference.stage1_candidates(one.embs, one.mask, q, **kw)
                 for q in qs]
        out["index_scope_equal"] = all(np.array_equal(
            w, reference.stage1_candidates(four.embs, four.mask, q, **kw))
            for w, q in zip(whole, qs))
        # Each block's own candidates, as one index of that block alone.
        block = lambda a, s: a[256 * s:256 * (s + 1)]
        own = [[(reference.stage1_candidates(
                    jax.device_put(block(one.embs, s)),
                    jax.device_put(block(one.mask, s)), q, **kw)
                 + 256 * s).tolist() for s in range(4)] for q in qs]
        out["shard_scope_is_union"] = all(
            reference.stage1_candidates(four.embs, four.mask, q,
                                        scope="shard", **kw).tolist()
            == sorted(sum(o, [])) for q, o in zip(qs, own))
        out["shard_scope_wider"] = all(len(sum(o, [])) > 32 for o in own)
        print(json.dumps(out))
    """)


def test_index_made_on_each_device_equals_the_one_device_index(index):
    assert index["embs_equal"] and index["mask_equal"]
    assert index["blocks"] == [[256 * s, 256 * (s + 1), s] for s in range(4)]
    assert index["mask_blocks"] == [[256 * s, s] for s in range(4)]
    # Built under a transfer guard that refuses any copy between devices.
    assert index["engine_holds_it"]


def test_reference_reads_each_block_where_it_lies(index):
    assert index["scores_equal"]
    assert index["index_scope_equal"]
    assert index["shard_scope_is_union"]
    assert index["shard_scope_wider"]


@pytest.fixture(scope="module")
def routed(tmp_path_factory):
    root = tmp_path_factory.mktemp("routed")
    return run_on_four(f"""
        import dataclasses
        import json
        import numpy as np
        import jax.numpy as jnp
        from bench import harness
        from bench_cells import ROUTED, routed_root, run

        class FailedShardDoc(harness.AsyncRetrievalEngine):
            # A degraded answer carries a document of the failed shard.
            def _finish_batch(self, prep, out):
                comps = super()._finish_batch(prep, out)
                first = self.corpus.docs_per_shard
                for i, c in enumerate(comps):
                    if c.coverage < 1:
                        ids = c.topk_ids.copy()
                        ids[-1] = first + 3
                        comps[i] = dataclasses.replace(c, topk_ids=ids)
                return comps

        class HealthyShardLeftOut(harness.AsyncRetrievalEngine):
            # Shard 2, healthy, is left out of every routed batch, and
            # its answers still report full coverage.
            def _prepare_batch_routed(self, *args, **kw):
                prep = super()._prepare_batch_routed(*args, **kw)
                if not self._started:
                    return prep
                a = list(prep.args)
                health = np.asarray(a[7]).copy()
                health[2] = False
                a[7] = jnp.asarray(health)
                return prep._replace(args=tuple(a))

        class RestoreDoesNothing(harness.AsyncRetrievalEngine):
            # The shard never comes back: service does not recover.
            def restore_shard(self, shard):
                pass

        class FailDoesNothing(harness.AsyncRetrievalEngine):
            # The failure is never applied: answers keep the shard.
            def fail_shard(self, shard):
                pass

        class FailsEarly(harness.AsyncRetrievalEngine):
            # Shard 1 is down from the start, before the mix fails it.
            def start(self):
                super().start()
                self.set_shard_health(1, False)

        root = routed_root({str(root)!r})
        out = {{name: run(root, name, seconds=2.5) for name in ROUTED}}
        out["failed_shard_doc"] = run(root, "small-routed-failover",
                                      seconds=2.5, engine_cls=FailedShardDoc)
        out["shard_left_out"] = run(root, "small-routed-failover",
                                    seconds=2.5,
                                    engine_cls=HealthyShardLeftOut)
        for name, cls in [("restore_does_nothing", RestoreDoesNothing),
                          ("fail_does_nothing", FailDoesNothing),
                          ("fails_early", FailsEarly)]:
            out[name] = run(root, "small-routed-failover", seconds=2.5,
                            engine_cls=cls)
        print(json.dumps(out))
    """)


@pytest.mark.parametrize("cell", ["small-routed", "small-routed-failover"])
def test_routed_twin_runs_by_name_and_is_correct(routed, cell):
    out = routed[cell]
    assert out["correct"], out["check"]
    assert out["device"]["count"] == 4
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"]["throughput_qps"]["value"] > 0
    assert list(out)[-1] == "check"


def test_answers_with_a_shard_down_are_degraded_and_correct(routed):
    out = routed["small-routed-failover"]
    failure = out["shard_failure"]
    assert failure["shard"] == 1 and failure["degraded"] > 0
    assert 0.5 <= failure["fail_s"] < failure["restore_s"] < 2.5
    assert out["check"]["lost"]["value"] == 0
    assert failure["misstated"] == 0
    assert "shard_failure" not in routed["small-routed"]


@pytest.mark.parametrize("fault", ["restore_does_nothing",
                                   "fail_does_nothing", "fails_early"])
def test_a_failover_that_does_not_follow_the_mix_fails_lost(routed, fault):
    # Coverage short while the shard is up, or full while it is down, is
    # judged by when each answer's batch read the shard's health.
    out = routed[fault]
    assert not out["correct"]
    assert out["shard_failure"]["misstated"] > 0
    assert out["check"]["lost"]["value"] >= out["shard_failure"]["misstated"]


def test_failed_shard_doc_in_a_degraded_answer_is_foreign(routed):
    out = routed["failed_shard_doc"]
    assert not out["correct"]
    assert out["check"]["foreign"]["value"] >= \
        out["shard_failure"]["degraded"] > 0


def test_healthy_shard_left_out_fails_miss_share(routed):
    out = routed["shard_left_out"]
    assert not out["correct"]
    assert out["check"]["foreign"]["value"] == 0
    assert out["check"]["miss_share"]["value"] > \
        out["check"]["miss_share"]["limit"]


def test_busy_time_is_averaged_over_the_devices():
    from bench.trace import Event, Reduction
    programs = [Event("jit_run(1)", 0, 8e9, 0), Event("jit_run(2)", 0, 8e9, 1),
                Event("jit_run(3)", 8e9, 1e9, 1)]
    red = Reduction(window=(0, 10e9), programs=programs, ops=[], host=[],
                    n_devices=2)
    assert red.busy_s == pytest.approx(8.5)
    assert red.gaps() == [(9e9, 10e9)]


@pytest.mark.parametrize("chips, axes, docs, why", [
    (4, [], 1024, "does not match"),
    (1, [["data", 4]], 1024, "does not match"),
    (4, [["model", 4]], 1024, "does not match"),
    (4, [["data", 4]], 1024 + 64, "not a multiple"),
])
def test_a_configuration_that_does_not_fit_its_chips_is_refused(
        chips, axes, docs, why):
    from bench.corpus import make_corpus
    cfg = {"corpus_docs": docs, "engine": {"mesh_axes": axes},
           "corpus": {"chunk_docs": 64}}
    with pytest.raises(ValueError, match=why):
        make_corpus(cfg, 1, chips=chips)


def test_quota_routing_is_out_of_scope():
    from bench.harness import stage1_scope
    assert stage1_scope({"engine": {}}) == "index"
    assert stage1_scope({"engine": {"stage1": "local"}}) == "shard"
    with pytest.raises(ValueError, match="stage1_total"):
        stage1_scope({"engine": {"stage1": "local", "stage1_total": 64}})
