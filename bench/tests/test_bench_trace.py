"""The trace reduction on a small trace recorded on one TPU v5e: 20 ms of
the text rerank cell's window (two batches of the step program)."""
import gzip
import os
import shutil

import pytest

from bench.kernels import fused_reveal
from bench.trace import instruction, module_name, op_name, reduce_xplane

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "text_rerank.xplane.pb.gz")


@pytest.fixture(scope="module")
def red(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(DATA) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return reduce_xplane(str(path))


def test_window_and_busy_time(red):
    assert red.n_devices == 1
    assert red.window_s == pytest.approx(0.020666509)
    assert red.busy_s == pytest.approx(0.018637996)
    assert 0 < red.busy_s <= red.window_s
    idle = sum(hi - lo for lo, hi in red.gaps()) / 1e9
    assert idle == pytest.approx(red.window_s - red.busy_s)


def test_programs_and_kernels_by_name(red):
    step = red.program_time("jit_run")
    assert step == pytest.approx([0.009109347, 0.008523162])
    launches = red.op_events("fused_reveal")
    assert len(launches) == 156
    shapes = {fused_reveal.cost(e.name) for e in launches}
    # one init launch shape (2560 rows, 1 token) and one round shape
    assert (2.0 * 64 * 8 * 128 * 128, 2398464.0) in shapes
    least = sum(c[1] / 819e9 for c in map(fused_reveal.cost, (
        e.name for e in launches)))
    took = sum(e.dur for e in launches) / 1e9
    assert 0 < least / took < 1


def test_breakdown_names_ops_and_labels_gaps(red):
    b = red.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    names = [n for n, _ in b["device_ops"]]
    assert "jit_run/fused_reveal.13" in names and "jit_run/fusion.217" in names
    secs = [t for _, t in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert all(lab.split(" | ")[0].startswith(("bench.", "no-span"))
               for lab, _ in b["idle_gaps"])


def test_names():
    text = "%fused_reveal.13 = (f32[64,1,8]) custom-call(s32[64] %a)"
    assert op_name(text) == "fused_reveal"
    assert instruction(text) == "fused_reveal.13"
    assert module_name("jit_run(878588376052493605)") == "jit_run"
