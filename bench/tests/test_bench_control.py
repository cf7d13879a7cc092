"""Every control, put in the program's place, fails the comparison's
limits of the cell it stands for."""
import os

from bench import harness
from bench.control import control_numbers
from bench_cells import BENCH, load, small_root


def test_controls_fail_the_text_rerank_limits(tmp_path):
    cell = harness.load_cell(small_root(tmp_path), "small-poisson")
    limits = load(os.path.join(BENCH, "traffic",
                               "text-rerank-poisson.json"))["limits"]
    rows = control_numbers(cell, seed=5)
    assert {r["control"] for r in rows} == {"bf16_query", "half_candidates",
                                            "int8_index"}
    for r in rows:
        assert any(r[n] > lim for n, lim in limits.items() if n in r), r
    half = next(r for r in rows if r["control"] == "half_candidates")
    assert half["inexact_share"] == 0 and half["foreign"] == 0


def test_controls_fail_the_stage1_limits(tmp_path):
    cell = harness.load_cell(small_root(tmp_path), "small-backlog")
    limits = load(os.path.join(BENCH, "traffic",
                               "text-stage1-backlog.json"))["limits"]
    rows = control_numbers(cell, seed=5)
    assert {r["control"] for r in rows} == {"bf16_query", "half_candidates"}
    for r in rows:
        assert any(r[n] > lim for n, lim in limits.items() if n in r), r
