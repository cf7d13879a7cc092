"""The readers of the engine's stage stamps and stage-1 spans, on synthetic
runs: values where the program gives them, None where it does not (a
program without the stamps or spans)."""
import types

import pytest

from bench import harness
from bench.trace import Event, Reduction
from repro.serve.engine import BatchRecord

STAGES = ("prepare_ms.rerank", "dispatch_wait_ms.rerank",
          "inflight_ms.rerank", "harvest_ms.rerank")


def reader(name):
    return harness.load_module(harness.CHECKOUT, "metrics", name).read


def record(t0, stage1_s=0.0, delivered=True):
    # released, prepared, dispatched, ready, done, delivered
    t = [t0, t0 + 1e-3, t0 + 3e-3, t0 + 6e-3, t0 + 10e-3, t0 + 10.75e-3]
    return BatchRecord(bucket=(32, 320), flavor="bandit", n_real=8,
                       occupancy=1.0, reveal_fraction=0.3, bid=int(t0),
                       t_release=t[0], t_prepared=t[1], stage1_s=stage1_s,
                       t_dispatched=t[2], t_ready=t[3], t_done=t[4],
                       t_delivered=t[5] if delivered else 0.0)


def run_of(batches, trace=None):
    return types.SimpleNamespace(batches=batches, trace=trace)


def test_stage_readers_sum_to_release_to_delivered():
    run = run_of([record(100.0), record(200.0), record(300.0,
                                                       delivered=False)])
    got = {m: reader(m)(run) for m in STAGES}
    assert got == pytest.approx({"prepare_ms.rerank": 1.0,
                                 "dispatch_wait_ms.rerank": 2.0,
                                 "inflight_ms.rerank": 3.0,
                                 "harvest_ms.rerank": 4.75})
    total = sum(b.t_delivered - b.t_release for b in run.batches[:2]) / 2
    assert sum(got.values()) == pytest.approx(total * 1e3)


@pytest.mark.parametrize("name", STAGES + ("stage1_wait_ms.stage1",))
def test_stamp_readers_silent_without_stamps(name):
    unstamped = types.SimpleNamespace(occupancy=1.0, reveal_fraction=0.3)
    assert reader(name)(run_of([unstamped])) is None
    assert reader(name)(run_of([])) is None


def test_stage1_wait_reads_mean_stage1_seconds():
    run = run_of([record(1.0, stage1_s=3.9), record(2.0, stage1_s=4.1)])
    assert reader("stage1_wait_ms.stage1")(run) == pytest.approx(4000.0)
    assert reader("stage1_wait_ms.stage1")(run_of([record(1.0)])) is None


def _reduction(program_starts, span_starts, window=(0.0, 10e9)):
    programs = [Event("jit_stage1(42)", s, 3.9e9) for s in program_starts]
    programs.append(Event("jit_run(7)", 5e9, 9e6))
    host = [Event("engine.stage1", s, 4e9) for s in span_starts]
    host.append(Event("engine.prepare", 0.5e9, 4e9))
    return Reduction(window=window, programs=programs, ops=[], host=host,
                     n_devices=1)


@pytest.mark.parametrize("programs,spans,want", [
    ([1e9, 5e9], [0.9e9, 4.9e9], 1.0),
    ([1e9, 3e9, 5e9], [0.9e9, 4.9e9], 1.5),
    # the piece of an execution under way when the trace started, and one
    # that starts after the last complete span, are not counted
    ([0.05e9, 1e9, 5e9, 9.5e9], [0.9e9, 4.9e9], 1.0),
    # a span that ends after the window is left out with its executions
    ([1e9, 5e9, 8.95e9], [0.9e9, 4.9e9, 8.9e9], 1.0),
])
def test_stage1_runs_per_batch(programs, spans, want):
    read = reader("stage1_runs_per_batch.stage1")
    assert read(run_of([], _reduction(programs, spans))) == \
        pytest.approx(want)


@pytest.mark.parametrize("programs,spans", [([1e9], []), ([], [1e9])])
def test_stage1_runs_per_batch_silent_without_runs_or_spans(programs,
                                                            spans):
    read = reader("stage1_runs_per_batch.stage1")
    assert read(run_of([], _reduction(programs, spans))) is None
    assert read(run_of([], None)) is None
