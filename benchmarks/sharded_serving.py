"""Sharded-corpus serving throughput: 1 / 4 / 16 shards on the CPU mesh.

The serving question this answers: when the (C, L, M) token index is
sharded over a real mesh and every shard runs the pooled frontier engine
over its OWN resident candidates (cross-shard traffic = K-sized scorecards
only), what does the corpus-resident pooled-bandit step sustain, and how is
frontier work distributed over the shards?

Each shard count runs in its own subprocess with that many XLA host
placeholder devices (the parent process must stay single-device, same
discipline as tests/_subproc.py), building the mesh via
``repro.launch.mesh.make_host_mesh``, a RAGGED ShardedCorpus (C chosen so
the tail shard is short — the valid_docs clamp is on the measured path),
and the ``make_sharded_serving_step`` bandit flavor.

Reported per shard count: queries/s, reveal fraction, per-shard bandit
round counts and frontier occupancy, plus a hard-bound (alpha_ef -> inf)
parity check against exact dense top-K — the acceptance gate.

Each worker additionally measures the full stage-1-inclusive pipeline both
ways (ISSUE 6): the GATHERED path (host full-corpus stage-1 kNN + numpy
``route_batch`` + the pre-routed shard_map step) against the ROUTED path
(``make_routed_serving_step``: centroid routing + shard-local stage-1 +
rerank in ONE shard_map dispatch), under a uniform query mix and a
Zipf-skewed one (queries drawn from Zipf(1.5)-popular documents, piling
routed mass onto the low shards). The second acceptance gate asserts the
4-shard routed pipeline sustains at least the gathered pipeline's cells/s
on the skewed mix — the host routing round-trip it deletes is genuinely
sequential, so this holds even though CPU shards timeshare one machine.

Caveat: on the CPU host platform the per-shard programs timeshare one
machine, so walltime does NOT improve with shard count here; the numbers
pin scheduling facts (rounds, occupancy, scorecard-only traffic) and give
the shape of the throughput curve a real mesh would see.

Registered in ``benchmarks/run.py`` as ``sharded``; standalone:

  PYTHONPATH=src python -m benchmarks.sharded_serving

Emits ``BENCH_sharded.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from typing import Dict

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _worker(n_shards: int, n_docs: int, B: int, N: int, T: int, L: int,
            M: int, k: int, alpha_ef: float, n_batches: int,
            seed: int) -> Dict:
    """Runs inside the subprocess that owns ``n_shards`` host devices."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.mesh import make_host_mesh
    from repro.retrieval.ann import generate_candidates_batch
    from repro.retrieval.service import (make_rerank_dense_step,
                                         make_routed_serving_step,
                                         make_sharded_serving_step)
    from repro.retrieval.sharded import (route_aligned, route_batch,
                                         route_candidates, shard_corpus)

    assert len(jax.devices()) == n_shards, (len(jax.devices()), n_shards)
    rng = np.random.default_rng(seed)
    emb = rng.standard_normal((n_docs, L, M)).astype(np.float32)
    emb /= np.linalg.norm(emb, axis=-1, keepdims=True)
    msk = np.arange(L)[None] < rng.integers(L // 2, L + 1, n_docs)[:, None]
    mesh = make_host_mesh(n_shards)
    sc = shard_corpus(emb, msk, mesh, n_centroids=8, router_seed=seed)

    def batch(i):
        r = np.random.default_rng(1000 + i)
        q = r.standard_normal((B, T, M)).astype(np.float32)
        q /= np.linalg.norm(q, axis=-1, keepdims=True)
        cand = np.stack([r.choice(n_docs, N, replace=False)
                         for _ in range(B)]).astype(np.int32)
        cand_l = route_candidates(cand, sc.docs_per_shard, sc.n_shards)
        # valid per-cell support: normalized docs x normalized query tokens
        a = np.full((B, N, T), -1.0, np.float32)
        b = np.ones((B, N, T), np.float32)
        a_l = route_aligned(a, cand, cand_l, sc.docs_per_shard)
        b_l = route_aligned(b, cand, cand_l, sc.docs_per_shard)
        return (q, cand, jnp.asarray(cand_l), jnp.asarray(a_l),
                jnp.asarray(b_l))

    step = jax.jit(make_sharded_serving_step(
        mesh, "bandit", topk=k, alpha_ef=alpha_ef, block_docs=8,
        block_tokens=4))
    vd = sc.valid_docs_device()

    batches = [batch(i) for i in range(n_batches)]
    q0, _, cl0, al0, bl0 = batches[0]
    jax.block_until_ready(step(sc.embs, sc.mask, jnp.asarray(q0), cl0, al0,
                               bl0, vd, jnp.int32(0)))        # compile+warm
    t0 = time.perf_counter()
    frac_sum, stats_last = 0.0, None
    for i, (q, _, cl, al, bl) in enumerate(batches):
        _, _, frac, stats = jax.block_until_ready(
            step(sc.embs, sc.mask, jnp.asarray(q), cl, al, bl, vd,
                 jnp.int32(i)))
        frac_sum += float(np.mean(np.asarray(frac)))
        stats_last = np.asarray(stats)
    wall = time.perf_counter() - t0

    # hard-bound parity vs exact dense, on the last batch
    hb = jax.jit(make_sharded_serving_step(
        mesh, "bandit", topk=k, alpha_ef=1e9, block_docs=8, block_tokens=4))
    q, cand, cl, al, bl = batches[-1]
    _, ids, _, _ = hb(sc.embs, sc.mask, jnp.asarray(q), cl, al, bl, vd,
                      jnp.int32(0))
    dense1 = make_rerank_dense_step(jax.make_mesh((1,), ("data",)), topk=k)
    _, want = dense1(jnp.asarray(emb), jnp.asarray(msk), jnp.asarray(q),
                     jnp.asarray(cand[:, None, :]))
    parity = all(set(np.asarray(ids)[b]) == set(np.asarray(want)[b])
                 for b in range(B))

    # --- routed vs gathered stage-1-inclusive pipelines (ISSUE 6) --------
    # Both serve the SAME budget of N candidates x T tokens per query, so
    # cells/s reduces to the walltime ratio; the gathered clock includes
    # the host stage-1 dispatch and the numpy routing round-trip the
    # routed step deletes.
    kprime = 8
    cells_per_batch = B * N * T
    routed_step = jax.jit(make_routed_serving_step(
        mesh, "bandit", topk=k, n_local=N, n_total=N, kprime=kprime,
        alpha_ef=alpha_ef, block_docs=8, block_tokens=4))
    cents, mass = sc.router.centroids, sc.router.shard_mass
    emb_d, msk_d = jnp.asarray(emb), jnp.asarray(msk)

    def gen(qq):
        return generate_candidates_batch(emb_d, msk_d, qq, kprime=kprime,
                                         max_candidates=N)

    def queries_uniform(i):
        r = np.random.default_rng(2000 + i)
        q = r.standard_normal((B, T, M)).astype(np.float32)
        return q / np.linalg.norm(q, axis=-1, keepdims=True)

    def queries_zipf(i):
        # Popularity-skewed traffic: query tokens sampled (with noise) from
        # Zipf(1.5)-favored documents, which live on the low shards under
        # the contiguous-block placement.
        r = np.random.default_rng(3000 + i)
        docs = np.minimum(r.zipf(1.5, size=B) - 1, n_docs - 1)
        tok = emb[docs[:, None], r.integers(0, L, (B, T))]     # (B, T, M)
        q = (tok + 0.2 * r.standard_normal((B, T, M))).astype(np.float32)
        return q / np.linalg.norm(q, axis=-1, keepdims=True)

    def time_routed(make_q):
        qs = [make_q(i) for i in range(n_batches)]
        jax.block_until_ready(routed_step(
            sc.embs, sc.mask, cents, mass, jnp.asarray(qs[0]), vd,
            jnp.int32(0)))
        t0 = time.perf_counter()
        stats_r = None
        for i, qq in enumerate(qs):
            _, _, _, stats = jax.block_until_ready(routed_step(
                sc.embs, sc.mask, cents, mass, jnp.asarray(qq), vd,
                jnp.int32(i)))
            stats_r = np.asarray(stats)
        wall_r = time.perf_counter() - t0
        qshare = stats_r[:, 3]
        return {
            "queries_per_s": B * n_batches / max(wall_r, 1e-9),
            "cells_per_s": cells_per_batch * n_batches / max(wall_r, 1e-9),
            "quota_share_mean": [float(x) for x in qshare],
            "routed_skew": float(np.max(qshare) * len(qshare)),
        }

    def time_gathered(make_q):
        qs = [make_q(i) for i in range(n_batches)]

        def one(qq, i):
            cand = jax.block_until_ready(gen(jnp.asarray(qq)))
            cand_l, (a_r, b_r) = route_batch(
                np.asarray(cand.doc_ids),
                [np.asarray(cand.a), np.asarray(cand.b)],
                sc.docs_per_shard, sc.n_shards, n_local=N)
            return jax.block_until_ready(step(
                sc.embs, sc.mask, jnp.asarray(qq), jnp.asarray(cand_l),
                jnp.asarray(a_r), jnp.asarray(b_r), vd, jnp.int32(i)))

        one(qs[0], 0)                                  # compile + warm
        t0 = time.perf_counter()
        for i, qq in enumerate(qs):
            one(qq, i)
        wall_g = time.perf_counter() - t0
        return {
            "queries_per_s": B * n_batches / max(wall_g, 1e-9),
            "cells_per_s": cells_per_batch * n_batches / max(wall_g, 1e-9),
        }

    routed, gathered = {}, {}
    for mix, make_q in (("uniform", queries_uniform),
                        ("zipf", queries_zipf)):
        gathered[mix] = time_gathered(make_q)
        routed[mix] = time_routed(make_q)
        routed[mix]["speedup_vs_gathered"] = (
            routed[mix]["cells_per_s"]
            / max(gathered[mix]["cells_per_s"], 1e-9))

    return {
        "n_shards": n_shards,
        "mesh": {a: int(n) for a, n in mesh.shape.items()},
        "docs_per_shard": sc.docs_per_shard,
        "valid_docs": [int(v) for v in sc.valid_docs],
        "queries_per_s": B * n_batches / max(wall, 1e-9),
        "wall_s": wall,
        "mean_reveal_fraction": frac_sum / n_batches,
        "shard_rounds": [float(x) for x in stats_last[:, 1]],
        "shard_occupancy": [float(x) for x in stats_last[:, 0]],
        "hard_bound_topk_parity": bool(parity),
        "gathered": gathered,
        "routed": routed,
    }


def run(shard_counts=(1, 4, 16), n_docs: int = 93, B: int = 8, N: int = 16,
        T: int = 8, L: int = 16, M: int = 16, k: int = 5,
        alpha_ef: float = 0.3, n_batches: int = 4, seed: int = 0,
        out: str = "BENCH_sharded.json") -> Dict:
    """Spawn one subprocess per shard count (each pins its own XLA host
    device count BEFORE importing jax) and collect the rows."""
    from benchmarks.common import cpu_worker_env
    envs = {s: cpu_worker_env(s) for s in shard_counts}
    rows = {}
    for s in shard_counts:
        cmd = [sys.executable, "-m", "benchmarks.sharded_serving",
               "--worker", str(s), "--n-docs", str(n_docs), "--batch",
               str(B), "--cands", str(N), "--tokens", str(T),
               "--doc-len", str(L), "--dim", str(M), "--topk", str(k),
               "--alpha-ef", str(alpha_ef), "--batches", str(n_batches),
               "--seed", str(seed)]
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=900, cwd=_ROOT, env=envs[s])
        if proc.returncode != 0:
            raise RuntimeError(f"{s}-shard worker failed:\n"
                               f"{proc.stderr[-3000:]}")
        row = json.loads(proc.stdout.strip().splitlines()[-1])
        rows[str(s)] = row
        print(f"{s:3d} shards: {row['queries_per_s']:8.1f} q/s  "
              f"reveal {row['mean_reveal_fraction']:.3f}  "
              f"rounds/shard {row['shard_rounds']}  "
              f"parity {row['hard_bound_topk_parity']}")
        for mix in ("uniform", "zipf"):
            g, r = row["gathered"][mix], row["routed"][mix]
            print(f"            {mix:7s}: gathered {g['cells_per_s']:10.0f} "
                  f"cells/s | routed {r['cells_per_s']:10.0f} cells/s "
                  f"({r['speedup_vs_gathered']:.2f}x, "
                  f"skew {r['routed_skew']:.2f})")

    accept = {"hard_bound_topk_parity_all":
              all(r["hard_bound_topk_parity"] for r in rows.values()),
              "every_shard_count_served":
              len(rows) == len(tuple(shard_counts))}
    if "4" in rows:
        # ISSUE 6 gate: deleting the host stage-1 + routing round-trip must
        # pay for itself on the 4-shard mesh under skewed traffic.
        accept["routed_beats_gathered_zipf_4shard"] = (
            rows["4"]["routed"]["zipf"]["cells_per_s"]
            >= rows["4"]["gathered"]["zipf"]["cells_per_s"])
    result = {
        "config": {"n_docs": n_docs, "B": B, "N": N, "T": T, "L": L, "M": M,
                   "k": k, "alpha_ef": alpha_ef, "n_batches": n_batches,
                   "shard_counts": list(shard_counts), "seed": seed},
        "shards": rows,
        "accept": accept,
    }
    if out:
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        print(f"wrote {out}")
    assert all(accept.values()), accept
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", type=int, default=0,
                    help="internal: run the measurement for N shards "
                         "in-process (device count set by the parent)")
    ap.add_argument("--n-docs", type=int, default=93)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--cands", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=8)
    ap.add_argument("--doc-len", type=int, default=16)
    ap.add_argument("--dim", type=int, default=16)
    ap.add_argument("--topk", type=int, default=5)
    ap.add_argument("--alpha-ef", type=float, default=0.3)
    ap.add_argument("--batches", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args(argv)
    if args.worker:
        row = _worker(args.worker, args.n_docs, args.batch, args.cands,
                      args.tokens, args.doc_len, args.dim, args.topk,
                      args.alpha_ef, args.batches, args.seed)
        print(json.dumps(row))
        return 0
    run(shard_counts=(1, 4) if args.quick else (1, 4, 16),
        n_docs=args.n_docs, B=args.batch, N=args.cands, T=args.tokens,
        L=args.doc_len, M=args.dim, k=args.topk, alpha_ef=args.alpha_ef,
        n_batches=args.batches, seed=args.seed)
    return 0


if __name__ == "__main__":
    sys.exit(main())
