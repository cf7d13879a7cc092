"""A cell added as files alone: a configuration and mixes in a temporary
directory, found by name, run end to end on the CPU at a small size."""
import os

from bench_cells import BENCH, load, run, small_root


def _snapshot():
    out = {}
    for d, _, files in os.walk(BENCH):
        for f in files:
            p = os.path.join(d, f)
            out[p] = os.path.getmtime(p)
    return out


def _listed(root, cell, key):
    spec = load(os.path.join(root, "BENCHMARK.json"))
    return {m["name"] for m in spec[key]
            if cell in m.get("workloads", [cell])}


def test_cell_from_new_files_runs_by_name(tmp_path):
    before = _snapshot()
    root = small_root(tmp_path)
    out = run(root, "small-poisson")
    assert out["correct"], out["check"]
    assert set(out["metrics"]) == _listed(root, "small-poisson",
                                          "end_to_end")
    assert out["attempted"] == 40 and out["failed"] == 0
    assert list(out)[-1] == "check"
    back = run(root, "small-backlog")
    assert back["correct"], back["check"]
    assert set(back["metrics"]) == _listed(root, "small-backlog",
                                           "end_to_end")
    assert "throughput_qps" in back["metrics"]
    assert back["metrics"]["throughput_qps"]["value"] > 0
    assert _snapshot() == before


def test_traced_run_reports_per_layer_counters(tmp_path):
    root = small_root(tmp_path)
    out = run(root, "small-poisson", trace=True)
    assert out["correct"], out["check"]
    # The CPU trace has no TPU plane: the device readers find nothing and
    # stay silent; the program's counters and the host clock are read.
    device = {"step_device_ms.rerank", "rerank_roofline.rerank",
              "fused_reveal_roofline.rerank", "device_idle_share.rerank"}
    host = _listed(root, "small-poisson", "per_layer") - device
    assert {"queue_wait_p95_ms.rerank", "reveal_rounds.rerank"} <= host
    assert set(out["metrics"]) - {"gc_pause_max_ms.rerank"} == \
        host - {"gc_pause_max_ms.rerank"}
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
