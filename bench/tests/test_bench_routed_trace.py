"""The routed cell's device-trace readers on a trace recorded on one TPU v5e
2x2 host: the 4-s traced slice of ``text-routed-4chip`` (seed 2160000011),
20 executions of the routed program ``jit_step`` on each chip.

Kept of the recording: every program execution (the XLA Modules lines),
the merge's collective operations and the two reshapes that read the
all-gathers' results (of some 850000 operation events), and the
``bench.*`` and ``engine.*`` host spans; the rest was dropped to keep the
file small. The readers read on it what they read on the whole recording.
"""
import gzip
import os
import shutil
import types

import numpy as np
import pytest

from bench import harness
from bench.routed import collective_s, is_collective, whole_runs
from bench.trace import module_name, op_name, reduce_xplane

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "routed_4chip.xplane.pb.gz")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def red(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(DATA) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return reduce_xplane(str(path))


def read(name, red, **kw):
    run = types.SimpleNamespace(trace=red, **kw)
    return harness.load_module(harness.CHECKOUT, "metrics", name).read(run)


def _executions(red, device):
    return sorted((e for e in red.programs if e.device == device
                   and module_name(e.name) == "jit_step"),
                  key=lambda e: e.start)


def test_executions_cut_by_the_trace_are_left_out(red):
    lo, hi = red.window
    runs = whole_runs(red)
    assert red.n_devices == 4 and len(runs) == 4 * 18
    for d in range(4):
        first, *inner, last = _executions(red, d)
        # The trace starts inside an execution on every chip: its first
        # one begins before the window and is recorded cut short.
        assert first.start < lo and first.dur / 1e6 < 90
        assert last.dur / 1e6 < 198
        assert all(207.2 < e.dur / 1e6 < 209.3 for e in inner)
    # Chip 0's last execution ends inside the window, cut at 195.2 ms by
    # the trace's stop: counting every execution inside the window reads
    # it as whole.
    assert read("routed_device_ms.routed", red) == pytest.approx(208.118,
                                                                 abs=0.001)
    assert np.mean(red.program_time("jit_step")) * 1e3 == pytest.approx(
        207.942, abs=0.001)


def test_roofline_is_one_chips_scan_over_the_routed_step(red):
    batches = [types.SimpleNamespace(n_real=8)]
    share = read("routed_roofline.routed", red, batches=batches,
                 corpus_shape=(524288, 128, 128), itemsize=2, chips=4,
                 cfg={"query_tokens": 32}, peaks=PEAKS)
    # 2 * 8 * 32 * 131072 * 128 * 128 operations at 197 TFLOP/s: 5.58 ms.
    assert share == pytest.approx(100 * 5.5812 / 208.118, rel=1e-3)


def test_merge_reads_the_collectives_inside_each_execution(red):
    names = {op_name(e.name) for e in red.ops if is_collective(e.name)}
    assert names == {"all-gather", "all-reduce"}
    # The reshapes that read an all-gather's result are not collectives.
    assert any(op_name(e.name) == "reshape" for e in red.ops)
    runs = whole_runs(red)
    per_run = collective_s(red, runs)
    assert all(11e-6 < t < 2e-3 for t in per_run)
    # Two all-gathers and one all-reduce (the two psums) take about 11.5 us
    # on the shard that reaches them last; the others wait there for it.
    by_chip = [sorted((r.start, t) for r, t in zip(runs, per_run)
                      if r.device == d) for d in range(4)]
    for batch in zip(*by_chip):
        assert min(t for _, t in batch) < 12.5e-6
    merge = read("merge_ms.routed", red)
    assert merge == pytest.approx(np.mean(per_run) * 1e3)
    assert merge == pytest.approx(0.1868, abs=1e-4)


def test_device_idle_share_averages_the_chips(red):
    assert read("device_idle_share.routed", red) == pytest.approx(
        0.000171, abs=1e-6)
