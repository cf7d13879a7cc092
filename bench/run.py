#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

  python3 bench/run.py --workload text-rerank-poisson --seed 1 \\
      --seconds 30 --trace 0

Set-up (the index made on the device from the seed, every program warmed)
is timed from process start; then the cell's traffic runs for
``--seconds``; then every answer is compared with the plain reference.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a traced slice of the window. The last line of
stdout is one JSON object; the compared numbers and their limits are the
last lines of stderr. The run refuses (non-zero exit, no result) unless
JAX's devices are TPUs, as many as the cell asks for, of a kind in
``bench/peaks.json``, with the kernels dispatched to Pallas.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import jax  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHECKOUT)

# The compile cache lives in the checkout, at a fixed path (the path is
# part of the cache key). It is set before the program is imported: the
# first compilation fixes the cache directory for the process, and
# importing the program compiles.
CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")
jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

from bench import check, harness  # noqa: E402


def refusal(chips: int, peaks: dict):
    """Why this process may not run the cell, or None."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return f"needs a TPU; JAX's first device is {devs[0]}"
    if len(devs) < chips:
        return f"the cell needs {chips} chips; JAX finds {len(devs)}"
    impl = os.environ.get("REPRO_KERNEL_IMPL", "auto")
    if impl not in ("auto", "pallas"):
        return f"REPRO_KERNEL_IMPL={impl!r} keeps the kernels off Pallas"
    if devs[0].device_kind not in peaks["devices"]:
        return (f"no peaks for device kind {devs[0].device_kind!r} in "
                "bench/peaks.json")
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(CHECKOUT, args.workload)
    peaks = harness.load_json(os.path.join(CHECKOUT, "bench", "peaks.json"))
    why = refusal(cell.workload["chips"], peaks)
    if why:
        print(f"bench: {why}", file=sys.stderr)
        return 2
    result = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), t_start=T_START,
        peaks=peaks["devices"][jax.devices()[0].device_kind])
    print(json.dumps(result), flush=True)
    check.report(result["check"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
