"""Fault-injected serving harness: supervision overhead + recovery + ladder.

Three measurements for the ISSUE 8 resilience layer:

  * ``overhead``  — the supervised async pipeline vs the bare one on an
    identical no-fault stream: the watchdog + engine-owned in-flight
    bookkeeping must be noise, not a tax (ratio recorded, not gated —
    single-core CI hosts timeshare the threads).
  * ``chaos``     — a 4-shard mesh run (subprocess, own device count)
    with a replayable FaultPlan: dispatch-thread kill plus a temporary
    shard outage mid-stream. Gates the RECOVERY facts, which are exact
    on any host: zero lost / zero duplicated completions, no error
    completions, restarts and failovers actually happened, coverage
    stayed in [0, 1], zero post-warmup recompiles (health mask and
    fidelity knobs are traced operands).
  * ``ladder``    — the deadline-aware degradation ladder on a bandit
    engine: squeezed deadlines must engage rungs > 0 (recorded per-rung
    batch counts) without a single recompile, and the degraded stream's
    mean reveal work must not exceed the comfortable stream's.

Registered in ``benchmarks/run.py`` as ``chaos``; standalone:

  PYTHONPATH=src python -m benchmarks.chaos_serving [--quick]

Emits ``BENCH_chaos.json``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Dict

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np

from benchmarks.common import cpu_worker_env


def _dataset(C, L, M, T, n_queries, seed):
    rng = np.random.default_rng(seed)
    embs = rng.standard_normal((C, L, M)).astype(np.float32)
    embs /= np.linalg.norm(embs, axis=-1, keepdims=True)
    mask = np.arange(L)[None] < rng.integers(max(3, L // 2), L + 1,
                                             C)[:, None]
    qs = rng.standard_normal((n_queries, T, M)).astype(np.float32)
    qs /= np.linalg.norm(qs, axis=-1, keepdims=True)
    return embs, mask, qs, rng


def supervision_overhead(n_requests: int = 256) -> Dict:
    """Same no-fault stream through supervise=False and supervise=True."""
    from repro.serve import AsyncRetrievalEngine, EngineConfig, Request

    embs, mask, qs, rng = _dataset(64, 8, 16, 8, 16, seed=0)
    cands = [rng.choice(64, 16, replace=False).astype(np.int32)
             for _ in range(n_requests)]
    out = {}
    # the first pass is a throwaway: it absorbs process-wide lazy init
    # (dispatch caches etc.) that would otherwise tax whichever variant
    # happens to run first and poison the ratio.
    for name, supervise in (("_warm", False), ("bare", False),
                            ("supervised", True)):
        eng = AsyncRetrievalEngine(embs, mask, EngineConfig(
            batch_size=8, deadline_s=0.02, token_buckets=(8,),
            cand_buckets=(16,), max_k=5, flavor="dense", pipeline_depth=2,
            supervise=supervise))
        eng.warmup()
        t0 = time.perf_counter()
        with eng:
            for i, c in enumerate(cands):
                eng.submit(Request(query=qs[i % 16], k=5, cand_ids=c))
            done = eng.drain()
        wall = time.perf_counter() - t0
        assert sorted(c.rid for c in done) == list(range(n_requests))
        assert eng.metrics.compiles_after_warmup == 0
        if name != "_warm":
            out[name] = {"wall_s": wall,
                         "qps": n_requests / max(wall, 1e-9)}
    out["overhead_ratio"] = out["bare"]["qps"] / max(
        out["supervised"]["qps"], 1e-9)
    return out


def ladder(n_requests: int = 64) -> Dict:
    """Squeezed vs comfortable deadlines through backpressure="degrade"."""
    from repro.serve import EngineConfig, Request, RetrievalEngine

    embs, mask, qs, rng = _dataset(96, 8, 16, 8, 16, seed=1)
    cands = [rng.choice(96, 32, replace=False).astype(np.int32)
             for _ in range(n_requests)]
    out = {}
    for name, deadline in (("comfortable", 1e6), ("squeezed", 1e-3)):
        eng = RetrievalEngine(embs, mask, EngineConfig(
            batch_size=8, token_buckets=(8,), cand_buckets=(32,), max_k=5,
            flavor="bandit", alpha_ef=0.3, block_docs=8, block_tokens=4,
            backpressure="degrade", deadline_headroom_s=0.05))
        eng.warmup()
        t0 = time.perf_counter()
        for i, c in enumerate(cands):
            eng.submit(Request(query=qs[i % 16], k=5, deadline_s=deadline,
                               cand_ids=c))
        done = eng.drain()
        wall = time.perf_counter() - t0
        levels = [b.degrade_level for b in eng.metrics.batches]
        out[name] = {
            "wall_s": wall,
            "qps": n_requests / max(wall, 1e-9),
            "mean_reveal_fraction": float(np.mean(
                [b.reveal_fraction for b in eng.metrics.batches])),
            "batches_per_rung": {str(l): levels.count(l)
                                 for l in sorted(set(levels))},
            "mean_degrade_level": float(np.mean(levels)),
            "compiles_after_warmup": eng.metrics.compiles_after_warmup,
        }
        assert len(done) == n_requests
    return out


def _chaos_worker(n_requests: int) -> Dict:
    """Mesh chaos run; the parent pinned 4 host devices before jax loaded."""
    from repro.dist.fault import FaultPlan, InjectedFault, poison_corpus
    from repro.serve import AsyncRetrievalEngine, EngineConfig, Request

    embs, mask, qs, rng = _dataset(47, 6, 8, 8, 32, seed=2)
    poisoned, rows = poison_corpus(embs, 0.01, seed=7, mode="nan")
    bad = int(np.flatnonzero(rows)[0])
    n_batches = n_requests // 8
    plan = FaultPlan([
        InjectedFault(point="dispatch", at=max(2, n_batches // 8),
                      action="kill"),
        InjectedFault(point="dispatch", at=max(4, n_batches // 4),
                      action="shard_down", arg=1),
        InjectedFault(point="dispatch", at=max(6, n_batches // 2),
                      action="shard_up", arg=1),
    ])
    eng = AsyncRetrievalEngine(poisoned, mask, EngineConfig(
        batch_size=8, deadline_s=0.02, token_buckets=(8,),
        cand_buckets=(16,), max_k=5, flavor="dense", pipeline_depth=2,
        supervise=True, max_thread_restarts=2,
        mesh_axes=(("data", 2), ("model", 2))), fault_plan=plan)
    eng.warmup()
    t0 = time.perf_counter()
    with eng:
        for i in range(n_requests):
            cand = rng.choice(47, 16, replace=False).astype(np.int32)
            if i % 10 == 0 and bad not in cand:
                cand[0] = bad
            eng.submit(Request(query=qs[i % 32], k=5, cand_ids=cand))
        done = eng.drain()
    wall = time.perf_counter() - t0
    rids = [c.rid for c in done]
    covs = [c.coverage for c in done]
    s = eng.metrics.summary()
    return {
        "n_requests": n_requests,
        "wall_s": wall,
        "qps": n_requests / max(wall, 1e-9),
        "lost": n_requests - len(set(rids)),
        "dup": len(rids) - len(set(rids)),
        "errors": s["errors"],
        "thread_restarts": s["thread_restarts"],
        "failovers": s["failovers"],
        "quarantined_total": s["quarantined_total"],
        "coverage_min": float(min(covs)),
        "coverage_mean": float(np.mean(covs)),
        "coverage_in_unit_interval": bool(
            all(0.0 <= c <= 1.0 for c in covs)),
        "fired": [f.action for f in plan.fired],
        "compiles_after_warmup": eng.metrics.compiles_after_warmup,
    }


def run(quick: bool = False, out: str = "BENCH_chaos.json") -> Dict:
    n = 128 if quick else 512
    env = cpu_worker_env(4)       # refuses up front off the CPU setup
    print("## supervision overhead (no faults)")
    overhead = supervision_overhead(n_requests=min(n, 256))
    print(f"bare {overhead['bare']['qps']:.1f} q/s | supervised "
          f"{overhead['supervised']['qps']:.1f} q/s "
          f"(ratio {overhead['overhead_ratio']:.2f})")

    print("## chaos recovery (4-shard mesh, kill + shard outage)")
    cmd = [sys.executable, "-m", "benchmarks.chaos_serving",
           "--worker", str(n)]
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=900,
                          cwd=_ROOT, env=env)
    if proc.returncode != 0:
        raise RuntimeError(f"chaos worker failed:\n{proc.stderr[-3000:]}")
    chaos = json.loads(proc.stdout.strip().splitlines()[-1])
    print(f"{chaos['qps']:.1f} q/s under chaos | restarts "
          f"{chaos['thread_restarts']} | failovers {chaos['failovers']} | "
          f"coverage min {chaos['coverage_min']:.2f} | "
          f"quarantined {chaos['quarantined_total']:.0f}")

    print("## degradation ladder (deadline squeeze)")
    lad = ladder(n_requests=32 if quick else 64)
    print(f"comfortable reveal {lad['comfortable']['mean_reveal_fraction']:.3f}"
          f" | squeezed reveal {lad['squeezed']['mean_reveal_fraction']:.3f} "
          f"rungs {lad['squeezed']['batches_per_rung']}")

    accept = {
        "chaos_zero_lost": chaos["lost"] == 0,
        "chaos_zero_dup": chaos["dup"] == 0,
        "chaos_zero_errors": chaos["errors"] == 0,
        "chaos_restart_happened": sum(
            chaos["thread_restarts"].values()) >= 1,
        "chaos_failover_happened": chaos["failovers"] >= 1,
        "chaos_coverage_in_unit_interval":
            chaos["coverage_in_unit_interval"],
        "chaos_quarantine_engaged": chaos["quarantined_total"] > 0,
        "chaos_zero_recompiles": chaos["compiles_after_warmup"] == 0,
        "ladder_engaged": any(int(r) > 0 for r in
                              lad["squeezed"]["batches_per_rung"]),
        "ladder_zero_recompiles":
            lad["squeezed"]["compiles_after_warmup"] == 0,
        "ladder_no_extra_reveal_work": (
            lad["squeezed"]["mean_reveal_fraction"]
            <= lad["comfortable"]["mean_reveal_fraction"] + 1e-6),
    }
    result = {"overhead": overhead, "chaos": chaos, "ladder": lad,
              "accept": accept}
    if out:
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        print(f"wrote {out}")
    assert all(accept.values()), accept
    return result


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", type=int, default=0,
                    help="internal: run the mesh chaos measurement "
                         "in-process (device count set by the parent)")
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args(argv)
    if args.worker:
        print(json.dumps(_chaos_worker(args.worker)))
        return 0
    run(quick=args.quick)
    return 0


if __name__ == "__main__":
    sys.exit(main())
