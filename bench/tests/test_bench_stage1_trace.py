"""The stage-1 reader and the idle-gap labels on a trace recorded on one
TPU v5e with the engine's spans: 14 s of the stage-1 cell's closed loop.
A batch's stage-1 span lasts 5.55 s there, so a shorter slice would hold
no complete one."""
import gzip
import os
import shutil
import types

import pytest

from bench import harness
from bench.trace import module_name, reduce_xplane

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "stage1_backlog.xplane.pb.gz")


@pytest.fixture(scope="module")
def red(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(DATA) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return reduce_xplane(str(path))


def test_one_stage1_execution_per_batch(red):
    read = harness.load_module(harness.CHECKOUT, "metrics",
                               "stage1_runs_per_batch.stage1").read
    assert read(types.SimpleNamespace(trace=red, batches=[])) == 1.0
    lo, hi = red.window
    starts = [e for e in red.programs if module_name(e.name) == "jit_stage1"
              and lo <= e.start < hi]
    (span,) = [e for e in red.host if e.name == "engine.stage1"]
    # Three executions start in the window, but the first and the last are
    # pieces cut by the trace's edges; the one whole execution lies in its
    # batch's span: 63 scan steps of about 84 ms.
    assert len(starts) == 3
    (whole,) = [e for e in starts
                if span.start <= e.start and e.end <= span.end]
    assert whole.dur / 1e9 == pytest.approx(5.549, abs=0.005)
    assert sum(e.dur for e in starts) / len(starts) / 1e9 < 4.7


def test_idle_gaps_are_labelled_by_engine_spans(red):
    lo, hi = red.window
    gaps = red.gaps()[:10]
    # The two longest are the window's edges, before the device's first
    # event and after its last, where no host span was recorded.
    assert {gaps[0][1], gaps[1][0]} == {lo, hi}
    assert all(red.label(*g) == "no-span" for g in gaps[:2])
    # Every other gap after the first recorded engine span names one.
    first = min(e.start for e in red.host if e.name.startswith("engine."))
    inner = [g for g in gaps[2:] if g[0] >= first]
    assert len(inner) == 7
    assert all(" | engine." in red.label(*g) for g in inner)
    assert 1 - red.busy_s / red.window_s == pytest.approx(0.00748,
                                                          abs=1e-5)
