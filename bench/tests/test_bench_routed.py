"""The routed cell's per-layer readers: the program counters on the 4-device
routed twin (``bench_cells.routed_root``), silent where the program keeps
no ``BatchRecord.shards_healthy``, and on batch records made by hand."""
import types

import pytest

from bench import harness
from four_devices import run_on_four

COUNTERS = ("shard_rounds_skew.routed", "recover_ms.routed")
DEVICE = ("routed_device_ms.routed", "routed_roofline.routed",
          "merge_ms.routed", "device_idle_share.routed")


def reader(name):
    return harness.load_module(harness.CHECKOUT, "metrics", name).read


@pytest.fixture(scope="module")
def twin(tmp_path_factory):
    root = tmp_path_factory.mktemp("routed_readers")
    return run_on_four(f"""
        import json
        from bench import harness
        from bench_cells import (CHECKOUT, ROUTED, load, routed_root, run,
                                 write)

        class NoShardCount(harness.AsyncRetrievalEngine):
            # A program without the counter, as before it was added.
            def _prepare_batch_routed(self, *args, **kw):
                prep = super()._prepare_batch_routed(*args, **kw)
                return prep._replace(shards_healthy=None)

        root = routed_root({str(root)!r})
        spec = load(f"{{root}}/BENCHMARK.json")
        for m in load(f"{{CHECKOUT}}/BENCHMARK.json")["per_layer"]:
            if m["name"].endswith(".routed"):
                spec["per_layer"].append(dict(m, workloads=list(ROUTED)))
        write(f"{{root}}/BENCHMARK.json", spec)
        out = {{name: run(root, name, seconds=2.5, trace=True)
               for name in ROUTED}}
        out["no_count"] = run(root, "small-routed-failover", seconds=2.5,
                              trace=True, engine_cls=NoShardCount)
        print(json.dumps(out))
    """)


def test_counters_read_on_the_failover_twin(twin):
    out = twin["small-routed-failover"]
    assert out["correct"], out["check"]
    m = out["metrics"]
    assert m["shard_rounds_skew.routed"]["value"] >= 1.0
    assert m["shard_rounds_skew.routed"]["unit"] == "ratio"
    # Recovered after the restore, well inside the rest of the window.
    assert 0 < m["recover_ms.routed"]["value"] < 1000
    # No TPU planes in a CPU trace: the device readers stay silent.
    assert not set(DEVICE) & set(m)


def test_recovery_is_not_read_without_a_failure(twin):
    m = twin["small-routed"]["metrics"]
    assert "shard_rounds_skew.routed" in m
    assert "recover_ms.routed" not in m


def test_counters_are_silent_without_the_program_counter(twin):
    out = twin["no_count"]
    assert out["correct"], out["check"]
    assert not set(COUNTERS) & set(out["metrics"])


def _batch(t_release, t_delivered, healthy, rounds=(10.0, 10.0, 10.0, 10.0)):
    return types.SimpleNamespace(t_release=t_release,
                                 t_delivered=t_delivered,
                                 shards_healthy=healthy, shard_rounds=rounds)


def _run(batches, failure=None):
    return types.SimpleNamespace(batches=batches, failure=failure, chips=4,
                                 trace=None)


def test_recovery_runs_to_the_first_full_batch_after_the_restore():
    failure = {"shard": 1, "fail_call": (10.0, 10.001),
               "restore_call": (20.0, 20.001)}
    batches = [_batch(9.5, 10.2, 4),      # read health before the failure
               _batch(19.7, 20.3, 3),     # prepared with the shard down
               _batch(19.9, 20.5, 3),
               _batch(20.1, 20.7, 4),     # the first full one
               _batch(20.3, 20.9, 4)]
    assert reader("recover_ms.routed")(_run(batches, failure)) == \
        pytest.approx(699.0)
    assert reader("recover_ms.routed")(_run(batches)) is None
    never = dict(failure, restore_call=None)
    assert reader("recover_ms.routed")(_run(batches, never)) is None


def test_skew_reads_the_batches_with_every_shard_healthy():
    batches = [_batch(0, 1, 4, (10.0, 10.0, 20.0, 20.0)),
               _batch(1, 2, 4, (10.0, 10.0, 10.0, 10.0)),
               _batch(2, 3, 3, (10.0, 0.0, 10.0, 10.0))]
    assert reader("shard_rounds_skew.routed")(_run(batches)) == \
        pytest.approx((20 / 15 + 1.0) / 2)
    assert reader("shard_rounds_skew.routed")(_run(batches[2:])) is None


@pytest.mark.parametrize("name", DEVICE)
def test_device_readers_are_silent_without_a_trace(name):
    assert reader(name)(_run([_batch(0, 1, 4)])) is None
