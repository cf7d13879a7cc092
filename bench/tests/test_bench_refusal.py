"""The benchmark refuses to run without a TPU, and without the program."""
import json
import os
import shutil
import subprocess
import sys

from bench_cells import BENCH, CHECKOUT


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "text-rerank-poisson",
         "--seed", str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out):
    for line in out.stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        raise AssertionError(f"printed a result: {line}")


def test_refuses_on_cpu():
    out = _run(CHECKOUT)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    _no_result(out)


def test_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    _no_result(out)
