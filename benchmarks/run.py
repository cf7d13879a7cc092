"""Benchmark entry point: one harness per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run              # everything
  PYTHONPATH=src python -m benchmarks.run --only table1
  PYTHONPATH=src python -m benchmarks.run --quick      # smaller corpus
  python benchmarks/run.py --list                      # enumerate harnesses

The roofline/dry-run analyses need 512 placeholder devices and live in
separate entry points:
  PYTHONPATH=src python -m repro.launch.dryrun --both --out results/dryrun.json
  PYTHONPATH=src python -m benchmarks.roofline --out results/roofline.json
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

# Script-friendly bootstrap: `python benchmarks/run.py` puts benchmarks/ on
# sys.path but neither the repo root (for `import benchmarks`) nor src (for
# `import repro`); add both so the module works as script and as -m target.
# benchmarks/ itself comes off the path: its compression.py would shadow
# the module of that name JAX imports.
_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != _HERE]
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np

# name -> (module, description).  perf_iterations / roofline are listed (and
# import-checked by --list) but run through their own __main__ entry points
# because they pin XLA_FLAGS for 512 placeholder devices at import.
HARNESSES = {
    "table1": ("benchmarks.table1_efficiency", "paper Table 1: efficiency"),
    "table2": ("benchmarks.table2_effectiveness",
               "paper Table 2: effectiveness"),
    "fig2": ("benchmarks.fig2_tradeoff", "paper Fig. 2: tradeoff curve"),
    "fig4": ("benchmarks.fig4_exploration", "paper Fig. 4: exploration"),
    "fig5": ("benchmarks.fig5_ann_bounds", "paper Fig. 5: ANN bounds"),
    "generalized": ("benchmarks.generalized_recsys",
                    "generalized bandit on recsys scorers"),
    "serving": ("benchmarks.serving_latency",
                "RetrievalEngine p50/p99 latency + throughput"),
    "serving_load": ("benchmarks.serving_load",
                     "open-loop Poisson load: goodput, sync vs async"),
    "reveal": ("benchmarks.reveal_throughput",
               "pooled frontier vs vmapped lockstep reveal engine"),
    "kernels": ("benchmarks.kernel_bench",
                "kernel-op block autotuning: tuned vs default tiles"),
    "sharded": ("benchmarks.sharded_serving",
                "corpus-sharded pooled-bandit serving, 1/4/16 shards"),
    "chaos": ("benchmarks.chaos_serving",
              "fault-injected serving: supervision, failover, ladder"),
    "compress": ("benchmarks.compression",
                 "compressed corpus: bytes/doc, dequant cells/s, fidelity"),
}
STANDALONE = {
    "perf_iterations": ("benchmarks.perf_iterations",
                        "§Perf hillclimb (own entry point, 512 fake devices)"),
    "roofline": ("benchmarks.roofline",
                 "roofline terms per cell (own entry point)"),
    "lint": ("repro.analysis.lint",
             "trace-safety + lockset lint "
             "(python -m repro.analysis.lint src)"),
}


def _to_jsonable(obj):
    if isinstance(obj, dict):
        return {str(k): _to_jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_to_jsonable(v) for v in obj]
    if isinstance(obj, (np.floating, np.integer)):
        return float(obj)
    return obj


def list_harnesses() -> int:
    """Import-check and print every harness. A broken import (like the
    repro.dist regression this guards against) fails loudly, per-module."""
    failures = 0
    print(f"{'name':16s} {'module':34s} description")
    for name, (module, desc) in {**HARNESSES, **STANDALONE}.items():
        try:
            importlib.import_module(module)
            status = desc
        except Exception as e:
            failures += 1
            status = f"[IMPORT FAILED] {type(e).__name__}: {e}"
        print(f"{name:16s} {module:34s} {status}")
    if failures:
        print(f"\n{failures} harness module(s) failed to import")
    return 1 if failures else 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=sorted(HARNESSES))
    ap.add_argument("--list", action="store_true",
                    help="list harnesses (import-checking each) and exit")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="results/bench.json")
    args = ap.parse_args(argv)

    if args.list:
        return list_harnesses()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    n_docs = 192 if args.quick else 384
    n_q = 6 if args.quick else 12

    from benchmarks import (chaos_serving, compression, fig2_tradeoff,
                            fig4_exploration, fig5_ann_bounds,
                            generalized_recsys, kernel_bench,
                            reveal_throughput, serving_latency, serving_load,
                            sharded_serving, table1_efficiency,
                            table2_effectiveness)
    benches = {
        "table1": lambda: table1_efficiency.run(n_docs, n_q),
        "table2": lambda: table2_effectiveness.run(n_docs, n_q),
        "fig2": lambda: fig2_tradeoff.run(n_docs, n_q),
        "fig4": lambda: fig4_exploration.run(min(n_docs, 256), min(n_q, 8)),
        "fig5": lambda: fig5_ann_bounds.run(min(n_docs, 256), min(n_q, 8)),
        "generalized": lambda: generalized_recsys.run(),
        "serving": lambda: serving_latency.run(
            n_docs=min(n_docs, 96),
            n_requests=24 if args.quick else 48,
            batch_sizes=(2, 4) if args.quick else (2, 4, 8),
            alphas=(0.3,) if args.quick else (0.15, 0.3, 1.0)),
        "serving_load": lambda: serving_load.run(smoke=args.quick),
        "reveal": lambda: reveal_throughput.run(
            Q=16 if args.quick else 64, n_docs=min(n_docs, 96)),
        "kernels": lambda: kernel_bench.run(quick=args.quick),
        # spawns one subprocess per shard count (each pins its own XLA
        # host device count), so it is safe to run from this single-device
        # process.
        "sharded": lambda: sharded_serving.run(
            shard_counts=(1, 4) if args.quick else (1, 4, 16)),
        # the mesh chaos measurement runs in its own subprocess (it pins 4
        # host devices), so it is safe from this single-device process.
        "chaos": lambda: chaos_serving.run(quick=args.quick),
        "compress": lambda: compression.run(quick=args.quick),
    }
    wanted = [args.only] if args.only else list(benches)

    results = {}
    for name in wanted:
        t0 = time.time()
        print(f"\n######## {name} ########")
        results[name] = benches[name]()
        print(f"[{name} done in {time.time()-t0:.1f}s]")

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(_to_jsonable(results), f, indent=1, default=str)
    print(f"\nwrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
