#!/usr/bin/env python3
"""Chip smoke run: the serving path once, end to end, on a TPU.

  python chip_smoke.py               # one chip: colbert-text, 131072 passages
  python chip_smoke.py --chips 4     # four chips: routed 4-way sharded index

One chip: a ``colbert-text`` index (32 query tokens, passages of up to 128
tokens, d=128, bf16) is generated on the device from ``--seed`` and served
through the normal entry points — ``RetrievalEngine`` and a started
``AsyncRetrievalEngine``, ``warmup()``, ``submit()``, ``drain()`` — in the
dense flavor and the Col-Bandit flavor at alpha_ef = 0.2. Half the requests
carry candidate lists of 64-256 docs; the other half go through on-device
stage-1. Every answer is compared with an exact f32 MaxSim top-5 over the
same candidates (``repro.kernels.ref``).

Four chips (``--chips 4``) run only the sharded path: a routed engine
(shard-local stage-1) over 4 x ``--n-docs`` passages, with one shard
failed and restored mid-stream, the same reference comparison, and a check
that each device holds a quarter of the index.

The script refuses to run — non-zero exit, no result line — unless JAX's
first device is a TPU and the kernels dispatch to Pallas
(``REPRO_KERNEL_IMPL`` unset or ``pallas``). Times it prints are smoke-run
figures, not benchmark numbers. The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.data.synthetic import DeviceCorpus, make_device_corpus  # noqa
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.quant import corpus_nbytes  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.retrieval.ann import (candidates_from_hits,  # noqa: E402
                                 generate_candidates)
from repro.serve.bucketing import pad_queries  # noqa: E402
from repro.serve.engine import (AsyncRetrievalEngine, Completion,  # noqa
                                EngineConfig, Request, RetrievalEngine)

TOPK = 5
DENSE_FLOOR = 0.99      # dense flavor: exact up to bf16-level ties
BANDIT_FLOOR = 0.9      # the paper's fidelity floor for Col-Bandit
# Dense scores vs the f32 reference, relative. The kernels' dots run at
# Precision.HIGHEST; a bf16 query moves the reference itself by about 5e-4
# (the control below), so 1e-4 tells the two apart. A dense swap is a tie
# if the reference scores agree to this bound.
SCORE_RTOL = 1e-4
STAGE1_CHECKS = 4       # stage-1 requests per engine rebuilt by a plain scan
PLAIN_SPAN = 16384      # docs per top_k of the plain stage-1 scan


@dataclasses.dataclass(frozen=True)
class Shape:
    """Sizes of one smoke run (the defaults are the chip run's)."""

    n_docs: int = 131072            # passages per chip
    n_requests: int = 64
    doc_len: int = 128
    dim: int = 128
    query_tokens: Tuple[int, int] = (8, 32)
    candidates: Tuple[int, int] = (64, 256)
    token_buckets: Tuple[int, ...] = (8, 16, 32)
    cand_buckets: Tuple[int, ...] = (64, 128, 256)
    batch_size: int = 8
    relevant: int = 4
    distractors: int = 24
    chunk_docs: int = 4096


def log(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def device_info() -> Dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def device_refusal(chips: int) -> Optional[str]:
    """Why this process may not run the smoke, or None."""
    info = device_info()
    if info["platform"] != "tpu":
        return f"needs a TPU; JAX's first device is {info}"
    if ops._impl() != "pallas":
        return ("needs the Pallas kernels; REPRO_KERNEL_IMPL="
                f"{os.environ.get('REPRO_KERNEL_IMPL')!r} selects "
                f"{ops._impl()!r}")
    if info["count"] < chips:
        return f"--chips {chips} needs {chips} devices; found {info}"
    return None


# -- traffic ----------------------------------------------------------------

def make_corpus(shape: Shape, seed: int, n_docs: int,
                sharding=None) -> DeviceCorpus:
    return make_device_corpus(
        n_docs=n_docs, n_queries=shape.n_requests, seed=seed,
        doc_len=shape.doc_len, min_doc_len=max(shape.doc_len // 4, 1),
        query_len=shape.query_tokens[1], dim=shape.dim,
        relevant_per_query=shape.relevant,
        distractors_per_query=shape.distractors,
        chunk_docs=shape.chunk_docs, sharding=sharding)


def make_requests(dc: DeviceCorpus, shape: Shape, seed: int,
                  block: int) -> List[Request]:
    """One request per planted query, of 8-32 tokens. Requests alternate
    in runs of ``block`` between a candidate list (the query's planted
    docs, docs of its topic, and random docs; 64-256 in all) and none
    (stage-1 finds the candidates)."""
    rng = np.random.default_rng(seed + 1)
    C = dc.n_docs
    reqs = []
    for i in range(shape.n_requests):
        t = int(rng.integers(shape.query_tokens[0], shape.query_tokens[1] + 1))
        cand = None
        if (i // block) % 2 == 0:
            n = int(rng.integers(shape.candidates[0], shape.candidates[1] + 1))
            ids = list(dc.relevant[i]) + list(dc.distractors[i])
            topical = np.flatnonzero(dc.doc_topic == dc.query_topic[i])
            ids += list(rng.choice(topical, size=min(len(topical), n // 2),
                                   replace=False))
            ids = list(dict.fromkeys(int(x) for x in ids))[:n]
            while len(ids) < n:
                x = int(rng.integers(C))
                if x not in ids:
                    ids.append(x)
            cand = rng.permutation(np.asarray(ids, np.int32))
        reqs.append(Request(query=dc.queries[i, :t], k=TOPK, cand_ids=cand))
    return reqs


# -- reference --------------------------------------------------------------

@jax.jit
def _ref_scores(embs, mask, q, ids):
    safe = jnp.maximum(ids, 0)
    docs = jnp.take(embs, safe, axis=0)
    dmask = jnp.take(mask, safe, axis=0) & (ids >= 0)[:, None]
    s = ref.maxsim_scores_ref(docs, dmask, q)
    return jnp.where(ids >= 0, s, -jnp.inf)


def local_shards(embs, mask) -> List[Tuple[int, int, jax.Array, jax.Array]]:
    """(first doc, end doc, embs, mask) of each device's block of rows."""
    key = lambda s: s.index[0].start or 0
    out = []
    for e, m in zip(sorted(embs.addressable_shards, key=key),
                    sorted(mask.addressable_shards, key=key)):
        lo = key(e)
        out.append((lo, lo + e.data.shape[0], e.data, m.data))
    return out


def reference_scores(shards, query: np.ndarray,
                     cand: np.ndarray) -> Dict[int, float]:
    """Exact f32 MaxSim score of every candidate, each scored on the
    device that holds it."""
    cand = np.unique(cand[cand >= 0])
    out: Dict[int, float] = {}
    width = 256 * max(1, -(-len(cand) // 256))
    with jax.default_matmul_precision("highest"):
        for lo, hi, e, m in shards:
            sel = cand[(cand >= lo) & (cand < hi)]
            if not sel.size:
                continue
            ids = np.full((width,), -1, np.int32)
            ids[:sel.size] = sel - lo
            s = np.asarray(_ref_scores(e, m, query.astype(np.float32), ids))
            out.update(zip(sel.tolist(), s[:sel.size].tolist()))
    return out


@functools.partial(jax.jit, static_argnames="kprime")
def _span_token_topk(embs, mask, q, kprime):
    n, L, M = embs.shape
    sims = q @ embs.reshape(n * L, M).astype(jnp.float32).T
    sims = jnp.where(mask.reshape(-1)[None, :], sims, -jnp.inf)
    return jax.lax.top_k(sims, kprime)


def plain_stage1(shards, query: np.ndarray, cfg: EngineConfig,
                 n_cand: int) -> np.ndarray:
    """The stage-1 candidates rebuilt without the chunked scan: one
    ``top_k`` per ``PLAIN_SPAN`` docs, merged on the host (best first,
    ties to the lower token position), then the same candidate rule."""
    ids = []
    for lo, hi, e, m in shards:
        L = e.shape[1]
        kprime = min(cfg.stage1_kprime, (hi - lo) * L)
        vals, pos = [], []
        for s in range(0, hi - lo, PLAIN_SPAN):
            v, p = _span_token_topk(e[s:s + PLAIN_SPAN], m[s:s + PLAIN_SPAN],
                                    query, kprime)
            vals.append(np.asarray(v))
            pos.append(np.asarray(p) + s * L)
        vals, pos = np.concatenate(vals, 1), np.concatenate(pos, 1)
        order = [np.lexsort((p, -v))[:kprime] for v, p in zip(vals, pos)]
        top = np.take_along_axis(vals, np.stack(order), 1)
        hits = np.take_along_axis(pos, np.stack(order), 1) // L
        cs = candidates_from_hits(jnp.asarray(top), jnp.asarray(hits),
                                  hi - lo, max_candidates=n_cand,
                                  support=cfg.support)
        local = np.asarray(cs.doc_ids)
        ids.append(local[local >= 0] + lo)
    return np.concatenate(ids)


def stage1_candidates(shards, query: np.ndarray, cfg: EngineConfig,
                      n_cand: int) -> np.ndarray:
    """The candidates the engine's stage-1 produces for this padded query:
    over the whole index on one chip; the union of each shard's own
    candidates on a routed mesh (no quota)."""
    ids = []
    for lo, _, e, m in shards:
        cs = generate_candidates(e, m, query, kprime=cfg.stage1_kprime,
                                 max_candidates=n_cand, support=cfg.support)
        local = np.asarray(cs.doc_ids)
        ids.append(local[local >= 0] + lo)
    return np.concatenate(ids)


def judge(name: str, comps: Sequence[Completion], reqs: Sequence[Request],
          rid_to_req: Dict[int, int], shards, cfg: EngineConfig,
          n_stage1: int, healthy_of: Optional[Callable[[int], np.ndarray]] = None,
          docs_per_shard: int = 0) -> Dict:
    """Top-5 overlap with the f32 reference, per served flavor. The first
    ``STAGE1_CHECKS`` stage-1 candidate sets are checked against
    :func:`plain_stage1`; dense scores against the reference, next to a
    control: the same reference with the query rounded to bf16."""
    overlaps: Dict[str, List[float]] = {"dense": [], "bandit": []}
    reveals: List[float] = []
    non_ties, score_err, control_err = 0, 0.0, 0.0
    cache: Dict[tuple, np.ndarray] = {}
    stage1_bad = []
    for c in comps:
        r = reqs[rid_to_req[c.rid]]
        tb = c.bucket[0]
        q = pad_queries([r.query], tb)[0]
        if r.cand_ids is None:
            key = (rid_to_req[c.rid], tb)
            if key not in cache:
                cache[key] = stage1_candidates(shards, q, cfg, n_stage1)
                if len(cache) <= STAGE1_CHECKS:
                    plain = plain_stage1(shards, q, cfg, n_stage1)
                    if set(plain.tolist()) != set(cache[key].tolist()):
                        stage1_bad.append(key[0])
            cand = cache[key]
        else:
            cand = np.asarray(r.cand_ids)
        if healthy_of is not None:
            healthy = healthy_of(c.rid)
            cand = cand[healthy[cand // docs_per_shard]]
        scores = reference_scores(shards, q, cand)
        order = sorted(scores, key=lambda d: (-scores[d], d))[:TOPK]
        got = [int(x) for x in c.topk_ids if x >= 0]
        flavor = c.flavor
        overlaps[flavor].append(len(set(got) & set(order)) / len(order))
        if flavor == "bandit":
            reveals.append(c.reveal_fraction)
        if flavor == "dense":
            kth = scores[order[-1]]
            q16 = q.astype(jnp.bfloat16).astype(np.float32)
            control = reference_scores(shards, q16, np.asarray(got))
            for d, s in zip(got, c.topk_scores):
                ref_s = scores.get(d, -np.inf)
                scale = max(1.0, abs(ref_s))
                score_err = max(score_err, abs(float(s) - ref_s) / scale)
                control_err = max(control_err,
                                  abs(control[d] - ref_s) / scale)
                if d not in order and ref_s < kth - SCORE_RTOL * max(
                        1.0, abs(kth)):
                    non_ties += 1
    out = {f: float(np.mean(v)) for f, v in overlaps.items() if v}
    detail = (f"; dense non-tie swaps {non_ties}, max score rel err "
              f"{score_err:.3e} (bf16-query control {control_err:.3e})"
              if "dense" in out else "")
    if reveals:
        detail += f"; bandit mean reveal fraction {np.mean(reveals):.4f}"
    log(f"{name}: top-{TOPK} overlap vs f32 reference "
        + ", ".join(f"{f} {v:.4f} over {len(overlaps[f])}"
                    for f, v in out.items())
        + f"{detail}; stage-1 sets rebuilt by a plain scan "
        f"{min(len(cache), STAGE1_CHECKS)}, differing {len(stage1_bad)}")
    failures = []
    if stage1_bad:
        failures.append(f"stage-1 candidates of requests {stage1_bad} "
                        "differ from a plain scan")
    if "dense" in out and out["dense"] < DENSE_FLOOR:
        failures.append(f"dense overlap {out['dense']:.4f} < {DENSE_FLOOR}")
    if non_ties:
        failures.append(f"{non_ties} dense swaps that are not ties")
    if score_err > SCORE_RTOL:
        failures.append(f"dense score error {score_err:.3e} > {SCORE_RTOL}")
    if "dense" in out and control_err <= SCORE_RTOL:
        failures.append(f"bf16-query control error {control_err:.3e} <= "
                        f"{SCORE_RTOL}: the score check cannot tell the "
                        "precisions apart")
    if "bandit" in out and out["bandit"] < BANDIT_FLOOR:
        failures.append(f"bandit overlap {out['bandit']:.4f} < "
                        f"{BANDIT_FLOOR}")
    if failures:
        raise AssertionError(f"{name}: " + "; ".join(failures))
    return out


# -- serving ----------------------------------------------------------------

def check_programs(eng: RetrievalEngine, require_kernels: bool) -> int:
    """Every warmed rerank program launches the Pallas kernels; returns
    the largest temp allocation any warmed program needs."""
    peak = 0
    for key in eng.compiled_buckets:
        exe = eng._executable(key)
        ma = exe.memory_analysis()
        if ma is not None:
            peak = max(peak, int(ma.temp_size_in_bytes))
        if (require_kernels and key[0] in ("step", "routed")
                and "tpu_custom_call" not in exe.as_text()):
            raise AssertionError(f"warmed program {key} has no Pallas "
                                 "kernel (tpu_custom_call)")
    return peak


def serve_stream(eng: RetrievalEngine, reqs: Sequence[Request],
                 events: Optional[Dict[int, Callable]] = None):
    """Submit every request and collect completions. ``events[i]`` runs
    after the requests before i are served and before i is submitted."""
    comps: List[Completion] = []
    rid_to_req: Dict[int, int] = {}
    health: Dict[int, np.ndarray] = {}
    for i, r in enumerate(reqs):
        if events and i in events:
            comps += eng.drain()
            events[i](eng)
        rid = eng.submit(r)
        rid_to_req[rid] = i
        if eng.shard_health() is not None:
            health[rid] = eng.shard_health()
    comps += eng.drain()
    return comps, rid_to_req, health


def check_stream(name: str, eng: RetrievalEngine, comps, rid_to_req) -> None:
    rids = [c.rid for c in comps]
    summary = eng.metrics.summary()
    failures = []
    if sorted(rids) != sorted(rid_to_req):
        failures.append(f"{len(set(rid_to_req) - set(rids))} lost, "
                        f"{len(rids) - len(set(rids))} duplicated rids")
    errors = [c.error for c in comps if c.error is not None]
    if errors:
        failures.append(f"{len(errors)} error completions: {errors[0]}")
    if summary["compiles_after_warmup"]:
        failures.append(f"{summary['compiles_after_warmup']} recompiles "
                        "after warmup")
    if failures:
        raise AssertionError(f"{name}: " + "; ".join(failures))


def run_engine(name: str, cls, dc: DeviceCorpus, cfg: EngineConfig,
               reqs: Sequence[Request], *, require_kernels: bool,
               events=None):
    t0 = time.perf_counter()
    eng = cls(dc.embs, dc.mask, cfg)
    t1 = time.perf_counter()
    eng.warmup()
    t2 = time.perf_counter()
    peak = check_programs(eng, require_kernels)
    started = isinstance(eng, AsyncRetrievalEngine)
    if started:
        eng.start()
    try:
        comps, rid_to_req, health = serve_stream(eng, reqs, events)
    finally:
        if started:
            eng.stop()
    t3 = time.perf_counter()
    check_stream(name, eng, comps, rid_to_req)
    log(f"{name}: build {t1 - t0:.2f} s, warmup {t2 - t1:.2f} s "
        f"({len(eng.compiled_buckets)} programs, largest temp "
        f"{peak / 2**20:.1f} MiB), served {len(comps)} requests in "
        f"{t3 - t2:.2f} s")
    return eng, comps, rid_to_req, health


def engine_config(shape: Shape, **kw) -> EngineConfig:
    # A long admission deadline: every batch is released full (or by
    # drain), so both engines see the same batches whatever the host's pace.
    return EngineConfig(batch_size=shape.batch_size, deadline_s=1.0,
                        token_buckets=shape.token_buckets,
                        cand_buckets=shape.cand_buckets, max_k=TOPK,
                        alpha_ef=0.2, **kw)


def serve_one_chip(shape: Shape, seed: int, *,
                   require_kernels: bool = True) -> Dict:
    """The one-chip phase: both flavors through the sync and the started
    async engine, judged against the f32 reference."""
    t0 = time.perf_counter()
    dc = make_corpus(shape, seed, shape.n_docs)
    jax.block_until_ready((dc.embs, dc.mask))
    log(f"corpus: {shape.n_docs} passages x {shape.doc_len} tokens x "
        f"{shape.dim} bf16 = {dc.embs.nbytes} bytes (+ mask "
        f"{dc.mask.nbytes}), made on device in "
        f"{time.perf_counter() - t0:.2f} s")
    reqs = make_requests(dc, shape, seed, block=1)
    shards = local_shards(dc.embs, dc.mask)
    result = {}
    for flavor in ("dense", "bandit"):
        cfg = engine_config(shape, flavor=flavor)
        for label, cls in (("sync", RetrievalEngine),
                           ("async", AsyncRetrievalEngine)):
            name = f"{label} {flavor}"
            eng, comps, rid_to_req, _ = run_engine(
                name, cls, dc, cfg, reqs, require_kernels=require_kernels)
            if eng.corpus_embs is not dc.embs:
                raise AssertionError(f"{name}: the engine copied the corpus")
            result[name] = judge(name, comps, reqs, rid_to_req, shards, cfg,
                                 eng._stage1_n)
            del eng
    result["resident_bytes"] = int(corpus_nbytes(dc.embs) + dc.mask.nbytes)
    return result


def serve_four_chips(shape: Shape, seed: int, *, n_shards: int = 4,
                     require_kernels: bool = True) -> Dict:
    """The sharded phase: a routed engine over ``n_shards`` x
    ``shape.n_docs`` passages, one shard failed and restored mid-stream."""
    mesh = make_mesh((n_shards,), ("data",))
    sharding = NamedSharding(mesh, P("data", None, None))
    t0 = time.perf_counter()
    dc = make_corpus(shape, seed, n_shards * shape.n_docs, sharding=sharding)
    jax.block_until_ready((dc.embs, dc.mask))
    log(f"corpus: {n_shards} x {shape.n_docs} passages, {dc.embs.nbytes} "
        f"bytes bf16, made on device in {time.perf_counter() - t0:.2f} s")
    cfg = engine_config(shape, flavor="auto",
                        bandit_min_candidates=shape.cand_buckets[1],
                        mesh_axes=(("data", n_shards),), stage1="local")
    reqs = make_requests(dc, shape, seed, block=shape.batch_size)
    third = len(reqs) // 3 // shape.batch_size * shape.batch_size
    events = {third: lambda e: e.fail_shard(1),
              2 * third: lambda e: e.restore_shard(1)}
    eng, comps, rid_to_req, health = run_engine(
        "routed", RetrievalEngine, dc, cfg, reqs,
        require_kernels=require_kernels, events=events)

    per_device = {}
    for s in eng.corpus_embs.addressable_shards:
        per_device[s.device] = per_device.get(s.device, 0) + s.data.nbytes
    want = corpus_nbytes(eng.corpus_embs) // n_shards
    log("resident index per device: " + ", ".join(
        f"{d.id}: {b} bytes" for d, b in per_device.items()))
    if len(per_device) != n_shards or any(b != want
                                          for b in per_device.values()):
        raise AssertionError(f"index not split evenly over {n_shards} "
                             f"devices: {per_device}")
    failed = [c for c in comps if not health[c.rid].all()]
    if not failed or not all(c.coverage < 1.0 for c in failed):
        raise AssertionError("the shard failover served no partial-coverage "
                             "requests")
    log(f"routed: {len(failed)} requests served with shard 1 down, "
        f"failovers {eng.metrics.summary()['failovers']}")
    shards = local_shards(eng.corpus_embs, eng.corpus_mask)
    result = {"routed": judge(
        "routed", comps, reqs, rid_to_req, shards, cfg, eng._stage1_n,
        healthy_of=health.get,
        docs_per_shard=eng.corpus.docs_per_shard)}
    result["resident_bytes_per_device"] = want
    return result


def memory_line() -> str:
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    peaks = [s.get("peak_bytes_in_use", 0) for s in stats]
    return f"peak_bytes_in_use per device {peaks}"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n-docs", type=int, default=Shape.n_docs,
                    help="passages per chip")
    args = ap.parse_args(argv)
    refusal = device_refusal(args.chips)
    if refusal:
        print(f"chip_smoke: {refusal}", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    shape = Shape(n_docs=args.n_docs)
    info = device_info()
    log(f"not a benchmark; device {info}, compile cache {cache}")
    t0 = time.perf_counter()
    if args.chips == 4:
        serve_four_chips(shape, args.seed)
    else:
        serve_one_chip(shape, args.seed)
    log(f"{memory_line()}; wall {time.perf_counter() - t0:.2f} s")
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
