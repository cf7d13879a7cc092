"""Run a snippet in a child process whose JAX has 4 CPU devices: the
parent's JAX keeps its one device, and a process's device count is fixed
when JAX starts."""
import json
import os
import subprocess
import sys
import textwrap

from bench_cells import CHECKOUT

TESTS = os.path.dirname(os.path.abspath(__file__))


def run_on_four(code: str, timeout: float = 600) -> dict:
    """Runs ``code`` and returns the JSON object its last stdout line
    prints."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [CHECKOUT, os.path.join(CHECKOUT, "src"), TESTS]))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         cwd=CHECKOUT, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])
