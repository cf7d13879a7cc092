"""Streaming retrieval serving engine with deadline-aware batching.

The request-serving loop the north-star asks for: a stream of
(query, deadline, k) requests is admitted through
:class:`repro.dist.fault.DeadlineBatcher` (release on full batch OR tightest
pending deadline), padded into a small set of static shape buckets
(:mod:`repro.serve.bucketing`) and dispatched through one of the
engine-facing rerank steps from :mod:`repro.retrieval.service`:

* ``dense``  — exact MaxSim over the candidate list,
* ``bandit`` — adaptive Col-Bandit reranking (reveal fraction << 1).

Every (flavor, token-bucket, candidate-bucket) pair is AOT-lowered and
compiled exactly once — ``warmup()`` pre-compiles every bucket so steady
state serves with ZERO recompiles; the executable cache and compile counts
are first-class (``engine.compiled_buckets``, ``metrics.compiles``) so tests
can assert the no-recompile property instead of trusting it.

Requests either carry a stage-1 candidate list (``cand_ids``) or the engine
runs its own stage-1 ANN (``repro.retrieval.ann.generate_candidates_batch``,
one scan per batch, also bucket-compiled) — the ANN path additionally yields
Eq. 15 per-cell bounds, which is what makes the bandit flavor effective.

The LM decode engine that used to live here moved to ``repro.serve.lm``.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import itertools
import os
import queue as queue_mod
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.analysis.hlo_audit import (AuditSpec, audit_executable,
                                      scorecard_budget_bytes)
from repro.core.frontier import FrontierState
from repro.dist.fault import (ChaosKill, DeadlineBatcher, FaultPlan,
                              apply_delay)
from repro.kernels import tuning
from repro.kernels.ops import autotune_op
from repro.kernels.quant import (CORPUS_FORMATS, corpus_nbytes,
                                 format_ordinal)
from repro.launch.mesh import make_mesh
from repro.retrieval.ann import generate_candidates_batch
from repro.retrieval.corpus import Corpus, build_corpus
from repro.retrieval.service import (init_stream_state,
                                     make_routed_serving_step,
                                     make_serving_step,
                                     make_sharded_serving_step,
                                     make_sharded_stage1,
                                     make_streaming_step)
from repro.retrieval.sharded import route_batch
from repro.serve.bucketing import (ShapeBuckets, pad_candidates, pad_queries,
                                   support_bounds)
from repro.serve.resilience import DegradeLadder, Supervisor
from repro.serve.lm import generate, serve_step  # noqa: F401  (back-compat)

SDS = jax.ShapeDtypeStruct


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static serving configuration (fixes the compiled shape set)."""

    batch_size: int = 8
    deadline_s: float = 0.02          # global admission deadline
    token_buckets: Tuple[int, ...] = (8, 16, 32)
    cand_buckets: Tuple[int, ...] = (32, 64)
    max_k: int = 10                   # compiled top-K width (per-request k <=)
    flavor: str = "auto"              # "dense" | "bandit" | "auto"
    bandit_min_candidates: int = 64   # auto: bandit when bucket >= this
    # Col-Bandit knobs (bandit flavor)
    alpha_ef: float = 0.3
    delta: float = 0.01
    block_docs: int = 8
    block_tokens: int = 8
    max_rounds: int = -1
    support: Tuple[float, float] = (0.0, 1.0)
    # Reveal engine for the bandit flavor: "pooled" (one cross-query
    # frontier loop, one fused reveal launch per round, converged queries
    # retired; falls back to the unfused chain under REPRO_KERNEL_IMPL=ref),
    # "pooled_fused"/"pooled_chain" (force one round body for A/B), or
    # "vmapped" (legacy per-query lockstep loop, kept for A/B).
    bandit_engine: str = "pooled"
    # Pooled engine only: let active queries grow their per-round doc block
    # up to this many docs out of slots freed by retired queries (0 = fixed
    # blocks, exact per-query parity with the solo bandit).
    max_block_docs: int = 0
    # Second growth axis: widen surviving slots' token blocks up to this
    # many tokens per selected doc out of freed frontier CELL capacity
    # (0 = fixed token blocks).
    max_block_tokens: int = 0
    # Kernel block-size autotuning (repro.kernels.tuning): when True,
    # warmup() times the candidate block configurations for every kernel
    # shape bucket the compiled executables will launch, BEFORE the AOT
    # compiles, so steady state serves with tuned tiles and still zero
    # recompiles. ``tuning_table`` names a JSON file: loaded (if present)
    # before any timing — covering entries are reused instead of re-timed
    # — and rewritten with the merged table after an autotune pass, so CI
    # and serving replicas share one tuned table.
    autotune: bool = False
    tuning_table: Optional[str] = None
    # Corpus mesh: () serves from one device (the seed path); a non-empty
    # axis spec like (("data", 2), ("model", 2)) builds that mesh, places
    # the corpus over EVERY axis as a ShardedCorpus (ragged tail padded +
    # tracked), and routes every bucket through the corpus-resident
    # shard_map steps — per-shard scorecards are the only cross-shard
    # traffic, and warmup()'s zero-recompile contract is unchanged.
    mesh_axes: Tuple[Tuple[str, int], ...] = ()
    # Resident corpus format (kernels.quant.CORPUS_FORMATS): "bf16" keeps
    # the corpus dense at its source dtype (the seed path, bit-identical
    # parity oracle); "int8" re-encodes it as per-(doc,token)-row symmetric
    # int8 + bf16 scales (~4x HBM reduction); "residual" stores a centroid
    # id + int8 residual against the spherical-k-means router codebook.
    # Dequantization happens INSIDE the scoring kernels — the compressed
    # payload is what crosses every program boundary, and the audit's
    # hlo-int8-residency rule asserts exactly that. Quantized engines
    # require candidate-carrying requests (stage-1 ANN scans raw token
    # rows) and are incompatible with stage1="local".
    corpus_format: str = "bf16"
    # stage-1 ANN (requests without a candidate list)
    stage1_kprime: int = 8
    stage1_candidates: int = 0        # 0 => smallest candidate bucket
    # Stage-1 placement on a sharded corpus: "host" is the legacy path
    # (host-side ANN over the full index + route_batch routing tables);
    # "local" runs the whole pipeline — centroid route -> shard-local kNN
    # -> Eq. 15 bounds -> rerank -> scorecard merge — inside ONE shard_map
    # (service.make_routed_serving_step): no host round-trip, candidate
    # embeddings never cross shards. Candidate-carrying requests always
    # use the host path (their ids are already global).
    stage1: str = "host"
    # "local" only: k-means centroid count for the skew-aware router built
    # at shard_corpus time, and the global per-query candidate budget the
    # router splits into per-shard quotas (0 = no quota: every shard emits
    # up to its full n_local — still shard-local, just not skew-aware).
    stage1_centroids: int = 8
    stage1_total: int = 0
    # "local" bandit only: seed the bandit with the stage-1 hit cells'
    # exact values (Eq. 15's exact-h branch) at zero reveal cost.
    prereveal_ann: bool = False
    # Admission headroom: a request's completion deadline minus the expected
    # batch service time (EMA of observed batches, floored by this) is what
    # the batcher gets — releasing AT the completion deadline would make
    # every deadline-triggered release a guaranteed miss under a real clock.
    deadline_headroom_s: float = 0.0
    # Async runtime (AsyncRetrievalEngine) knobs — inert on the sync engine.
    # ``pipeline_depth`` bounds the batches in flight on the device plus
    # prepared-but-undispatched batches queued behind them: depth 2 means
    # batch i+1 dispatches while i executes (the JetStream-style overlap);
    # 1 degenerates to synchronous dispatch.
    pipeline_depth: int = 2
    # Backpressure policy when a deadline-carrying request's projected
    # completion (now + (backlog + 1) * expected service) already overruns
    # its deadline at submit: "none" admits anyway (it will simply miss),
    # "reject" raises AdmissionRejected, "degrade" truncates the request's
    # candidate list to the smallest candidate bucket (a cheaper, already
    # compiled shape) and admits — dense requests and stage-1 requests
    # cannot be degraded and fall back to plain admission.
    backpressure: str = "none"
    # Continuous (slot-refill) batching: serve through ONE resumable
    # streaming executable instead of batch-at-a-time dispatch. A retired
    # query's frontier slots are refilled from the admission queue
    # mid-flight (``retrieval.service.make_streaming_step``); the stream
    # advances ``stream_trip_limit`` reveal rounds per device dispatch.
    continuous: bool = False
    stream_trip_limit: int = 4
    # Self-healing runtime (AsyncRetrievalEngine): when ``supervise`` is
    # set, a watchdog (serve.resilience.Supervisor) restarts dead pipeline
    # threads up to ``max_thread_restarts`` each; in-flight work survives
    # restarts because dispatch/admission state lives on the engine, and
    # completion delivery is rid-deduplicated (zero lost, zero duplicated).
    # Budget exhaustion escalates to the loud thread-death failure the
    # unsupervised engine raises immediately.
    supervise: bool = False
    max_thread_restarts: int = 2
    supervise_interval_s: float = 0.02
    # Deadline-aware fidelity ladder (``backpressure="degrade"``, bandit
    # flavor): when a batch's tightest deadline headroom — (deadline - now)
    # / expected service time — drops below headrooms[i], the batch runs
    # with alpha_ef scaled by degrade_alpha_scales[i] and (rung >= 2) the
    # reveal rounds capped at degrade_round_caps[i]. The knobs are traced
    # scalars on the always-lowered executables: changing rungs never
    # recompiles, and rung 0 is bit-identical to the undegrade trace.
    degrade_headrooms: Tuple[float, ...] = (1.0, 0.5, 0.25)
    degrade_alpha_scales: Tuple[float, ...] = (2.0, 4.0, 8.0)
    degrade_round_caps: Tuple[int, ...] = (0, 8, 4)
    seed: int = 0
    # Compile-contract auditing (repro.analysis.hlo_audit): when set,
    # warmup() walks every AOT executable's optimized HLO and raises
    # AuditError (with op provenance) on any host sync, f64 math,
    # f32-resident corpus promotion, over-budget collective traffic
    # (scorecard merge + two scalar psums is the sharded contract) or
    # peak temp buffers past ``audit_peak_bytes`` (0 = a generous
    # corpus-derived bound). ``audit_require_bf16`` additionally treats a
    # non-bf16 corpus itself as a promotion-contract violation.
    audit: bool = False
    audit_peak_bytes: int = 0
    audit_require_bf16: bool = False


class AdmissionRejected(RuntimeError):
    """Raised by ``submit`` under ``backpressure="reject"``: the queue is
    deep enough that the request's completion deadline is already
    unmeetable at admission time."""


@dataclasses.dataclass
class Request:
    """One retrieval request: (query, deadline, k)."""

    query: np.ndarray                       # (T, M) float32 token embeddings
    k: int = 10
    deadline_s: Optional[float] = None      # completion deadline (arrival-rel)
    cand_ids: Optional[np.ndarray] = None   # (n,) global doc ids; None=stage-1
    # filled in by the engine
    rid: int = -1
    arrival: float = 0.0
    # Absolute completion deadline (clock frame), stamped once at admission.
    # Equivalent to arrival + deadline_s, but carried explicitly so the
    # serve-time miss decision (t_done > deadline_abs) has exactly one
    # source of truth — the contract the stale-next_expiry admission test
    # pins down.
    deadline_abs: Optional[float] = None
    # Fraction of the request's ORIGINAL candidate list that survived
    # admission (backpressure="degrade" truncation); multiplies into the
    # completion's coverage so a degraded answer is visibly partial.
    coverage_scale: float = 1.0


@dataclasses.dataclass
class Completion:
    rid: int
    topk_ids: np.ndarray          # (k,) global doc ids, -1 padded
    topk_scores: np.ndarray       # (k,) f32
    queue_wait_s: float           # admission latency
    latency_s: float              # arrival -> results materialized
    deadline_miss: bool
    flavor: str
    bucket: Tuple[int, int]       # (token_bucket, cand_bucket)
    reveal_fraction: float        # fraction of MaxSim cells computed
    # Fraction of the request's candidate universe actually searched:
    # 1.0 on a fully healthy serve; < 1 when a failed shard's documents
    # were masked out of the merge (candidate-mass fraction on healthy
    # shards) or admission truncated the candidate list (coverage_scale).
    # 0.0 on an ``error`` completion — nothing was searched.
    coverage: float = 1.0
    # Fidelity-ladder rung this request's batch ran at (0 = full fidelity).
    degrade_level: int = 0
    # Loud-failure surface: None on a served completion; the failure
    # reason when the engine could not serve the request (stopped with
    # work queued and flushing impossible, supervision budget exhausted,
    # continuous-mode slot lost to a thread restart). topk_ids are all -1.
    error: Optional[str] = None
    # Ordinal of the batch that served the request (its BatchRecord.bid and
    # the ``bid`` of its batch's profiler spans); -1 on an error completion.
    bid: int = -1


@dataclasses.dataclass
class BatchRecord:
    bucket: Tuple[int, int]
    flavor: str
    n_real: int
    occupancy: float              # n_real / batch_size
    reveal_fraction: float
    # Reveal-engine diagnostics (service.py stats vector): live-slot
    # fraction of the pooled frontier (or lockstep duty cycle for the
    # vmapped engine), per-query reveal rounds actually attributable to
    # queries, and the rounds a lockstep loop would have wasted on
    # already-converged queries. Dense batches report (1, 0, 0).
    # On a sharded corpus these aggregate over shards (mean occupancy of
    # the shards that did bandit work, summed rounds/waste) and the raw
    # per-shard vectors land in shard_occupancy / shard_rounds.
    frontier_occupancy: float = 1.0
    total_rounds: float = 0.0
    lockstep_waste: float = 0.0
    shard_occupancy: Optional[Tuple[float, ...]] = None
    shard_rounds: Optional[Tuple[float, ...]] = None
    # Routed (shard-local stage-1) batches only: each shard's mean routed
    # quota share over the batch's queries (columns sum to ~1 across
    # shards; uniform = 1/n_shards). The skew signal metrics.summary()
    # aggregates into routed_quota_share_mean / routed_skew.
    shard_quota_share: Optional[Tuple[float, ...]] = None
    # Mesh engines only: how many shards were healthy in the health
    # snapshot the batch was prepared with (None off-mesh).
    shards_healthy: Optional[int] = None
    # (doc, query) cells quarantined by the finite-score guard (poisoned
    # corpus rows surfacing NaN/Inf MaxSim values), summed over shards.
    quarantined: float = 0.0
    # Fidelity-ladder rung the batch ran at (0 = full fidelity).
    degrade_level: int = 0
    # Batch ordinal (the ``bid`` of its completions and profiler spans) and
    # its stage stamps on the engine clock: released by the batcher,
    # prepared (bucketed, padded, stage-1 done), launched on the device,
    # results ready, copied to the host, completions delivered. A stage a
    # path lacks carries its neighbour's stamp (the continuous stream
    # prepares before its release stamp and copies after ``t_done``).
    # ``stage1_s`` is the time spent in stage-1 (0.0 without it).
    bid: int = -1
    t_release: float = 0.0
    t_prepared: float = 0.0
    stage1_s: float = 0.0
    t_dispatched: float = 0.0
    t_ready: float = 0.0
    t_done: float = 0.0
    t_delivered: float = 0.0

    @property
    def service_s(self) -> float:
        """Release -> results materialized (feeds the service-time EMA)."""
        return self.t_done - self.t_release


class EngineMetrics:
    """Serving metrics: per-request, per-batch, and compile accounting.

    Mutations go through the ``record_*`` methods, which take an internal
    lock — the async engine's admit, dispatch and caller threads all write
    here concurrently. ``summary()`` snapshots under the same lock."""

    def __init__(self):
        self._lock = threading.Lock()
        self.completions: List[Completion] = []
        self.batches: List[BatchRecord] = []
        # Recorded batches whose completions are not yet delivered, by bid.
        self._undelivered: Dict[int, BatchRecord] = {}
        self.compiles: Dict[tuple, int] = {}
        self.compiles_after_warmup: int = 0
        # Backpressure accounting (async engine): requests refused outright
        # and requests admitted with a truncated candidate list.
        self.rejected: int = 0
        self.degraded: int = 0
        # Warmup-time kernel autotuning accounting: wall seconds spent
        # timing candidates, buckets measured this warmup, and entries
        # reused from a persisted tuning table instead of re-timed.
        self.autotune_s: float = 0.0
        self.autotune_buckets: int = 0
        self.tuning_entries_loaded: int = 0
        # Resilience accounting: shard-health transitions to unhealthy,
        # the live per-shard health vector (None off-mesh), and serving
        # threads restarted by the supervision watchdog.
        self.failovers: int = 0
        self.shard_health: Optional[List[bool]] = None
        self.thread_restarts: Dict[str, int] = {}

    def record_compile(self, key: tuple, after_warmup: bool) -> None:
        with self._lock:
            self.compiles[key] = self.compiles.get(key, 0) + 1
            if after_warmup:
                self.compiles_after_warmup += 1

    def record_batch(self, record: BatchRecord,
                     completions: Sequence[Completion]) -> None:
        with self._lock:
            self.batches.append(record)
            self.completions.extend(completions)
            self._undelivered[record.bid] = record

    def record_delivered(self, bid: int, t: float) -> None:
        """Stamp ``t_delivered`` on the recorded batch ``bid``."""
        with self._lock:
            record = self._undelivered.pop(bid, None)
            if record is not None:
                record.t_delivered = t

    def record_rejected(self) -> None:
        with self._lock:
            self.rejected += 1

    def record_degraded(self) -> None:
        with self._lock:
            self.degraded += 1

    def record_failover(self) -> None:
        with self._lock:
            self.failovers += 1

    def record_shard_health(self, healthy: Sequence[bool]) -> None:
        with self._lock:
            self.shard_health = [bool(h) for h in healthy]

    def record_restart(self, name: str) -> None:
        with self._lock:
            self.thread_restarts[name] = self.thread_restarts.get(name, 0) + 1

    def summary(self) -> Dict[str, Any]:
        with self._lock:
            reqs, bats = list(self.completions), list(self.batches)
            n_compiles = int(sum(self.compiles.values()))
            n_after = int(self.compiles_after_warmup)
            n_rej, n_deg = self.rejected, self.degraded
            n_fail = self.failovers
            health = (None if self.shard_health is None
                      else list(self.shard_health))
            restarts = dict(self.thread_restarts)
        bandit_bats = [b for b in bats if b.flavor == "bandit"]
        waits = np.array([c.queue_wait_s for c in reqs]) if reqs else np.zeros(1)
        lats = np.array([c.latency_s for c in reqs]) if reqs else np.zeros(1)
        return {
            "n_requests": len(reqs),
            "n_batches": len(bats),
            "queue_wait_p50_ms": float(np.percentile(waits, 50) * 1e3),
            "queue_wait_p99_ms": float(np.percentile(waits, 99) * 1e3),
            "latency_p50_ms": float(np.percentile(lats, 50) * 1e3),
            "latency_p99_ms": float(np.percentile(lats, 99) * 1e3),
            "deadline_miss_rate": (float(np.mean([c.deadline_miss
                                                  for c in reqs]))
                                   if reqs else 0.0),
            "mean_occupancy": (float(np.mean([b.occupancy for b in bats]))
                               if bats else 0.0),
            "mean_reveal_fraction": (float(np.mean([b.reveal_fraction
                                                    for b in bats]))
                                     if bats else 0.0),
            # Bandit batches only: dense batches report a placeholder 1.0
            # that would dilute the frontier diagnostic under mixed traffic.
            "mean_frontier_occupancy": (float(np.mean(
                [b.frontier_occupancy for b in bandit_bats]))
                if bandit_bats else 0.0),
            "total_reveal_rounds": float(sum(b.total_rounds for b in bats)),
            "total_lockstep_waste": float(sum(b.lockstep_waste
                                              for b in bats)),
            "compiles": n_compiles,
            "compiles_after_warmup": n_after,
            "rejected": int(n_rej),
            "degraded": int(n_deg),
            "autotune_s": float(self.autotune_s),
            "autotune_buckets": int(self.autotune_buckets),
            "tuning_entries_loaded": int(self.tuning_entries_loaded),
            # Resilience surface: quarantined poisoned cells, mean answer
            # coverage (served completions only — error completions carry
            # coverage 0 but no search), ladder activity, failovers, the
            # live shard-health vector, and watchdog restarts.
            "quarantined_total": float(sum(b.quarantined for b in bats)),
            "mean_coverage": (float(np.mean([c.coverage for c in reqs
                                             if c.error is None] or [1.0]))),
            "errors": int(sum(1 for c in reqs if c.error is not None)),
            "ladder_degraded_batches": int(sum(1 for b in bats
                                               if b.degrade_level > 0)),
            "failovers": int(n_fail),
            **({"shard_healthy": health} if health is not None else {}),
            "thread_restarts": restarts,
            **self._shard_summary(bats),
        }

    def _shard_summary(self, bats: List[BatchRecord]) -> Dict[str, Any]:
        """Per-shard aggregates over the sharded-corpus batches: summed
        bandit rounds and mean frontier occupancy per shard — the routing
        skew / straggler signal the mesh operator watches."""
        sharded = [b for b in bats if b.shard_rounds is not None]
        if not sharded:
            return {}
        rounds = np.sum([b.shard_rounds for b in sharded], axis=0)
        occ = np.mean([b.shard_occupancy for b in sharded], axis=0)
        out = {
            "n_shards": len(rounds),
            "shard_rounds_total": [float(r) for r in rounds],
            "shard_occupancy_mean": [float(o) for o in occ],
        }
        routed = [b for b in sharded if b.shard_quota_share is not None]
        if routed:
            qs = np.mean([b.shard_quota_share for b in routed], axis=0)
            # skew = hottest shard's share relative to a uniform split
            # (1.0 = perfectly balanced routing, n_shards = worst case).
            out["routed_quota_share_mean"] = [float(q) for q in qs]
            out["routed_skew"] = float(np.max(qs) * len(qs))
        return out


class _Prepared(NamedTuple):
    """A released batch after host-side preparation (bucketing, padding,
    stage-1, routing): everything the dispatch thread needs to launch the
    device program and the harvest step needs to attribute results."""

    real: List[Request]
    n_real: int
    bucket: Tuple[int, int]
    flavor: str
    exe: Any
    args: tuple
    t_release: float
    # Batch ordinal: the idempotency key the supervised dispatch path uses
    # to guarantee a batch is harvested exactly once across thread restarts.
    bid: int = -1
    # Per-real-request fraction of candidate mass on HEALTHY shards at
    # prepare time (None = fully healthy, i.e. all 1.0).
    coverage: Optional[np.ndarray] = None
    degrade_level: int = 0
    # Stage stamps carried to the batch's BatchRecord (engine clock).
    t_prepared: float = 0.0
    stage1_s: float = 0.0
    t_dispatched: float = 0.0
    # Healthy shards in the snapshot ``coverage`` was computed from.
    shards_healthy: Optional[int] = None


class RetrievalEngine:
    """Deadline-batched, shape-bucketed late-interaction serving loop.

    Typical use::

        engine = RetrievalEngine(doc_embs, doc_mask, EngineConfig(...))
        engine.warmup()                        # compile every bucket
        rid = engine.submit(Request(query=q, k=5, deadline_s=0.05))
        done = engine.poll()                   # [] until a batch releases
        done += engine.drain()                 # end of stream: flush queue

    ``clock`` is injectable so tests and simulations drive virtual time.

    Batch execution is staged as prepare (host: bucket, pad, stage-1,
    route) -> dispatch (launch the AOT executable; returns device arrays
    without blocking) -> finish (block_until_ready + attribution). This
    engine runs the three stages back to back per batch — the synchronous
    parity oracle; :class:`AsyncRetrievalEngine` runs them on a pipeline.
    """

    def __init__(self, corpus_embs, corpus_mask,
                 config: Optional[EngineConfig] = None, *,
                 clock: Callable[[], float] = time.monotonic):
        self.cfg = config or EngineConfig()
        self.clock = clock
        if self.cfg.stage1 not in ("host", "local"):
            raise ValueError(f"unknown stage1 placement {self.cfg.stage1!r} "
                             "(expected 'host' or 'local')")
        if self.cfg.corpus_format not in CORPUS_FORMATS:
            raise ValueError(
                f"unknown corpus_format {self.cfg.corpus_format!r} "
                f"(expected one of {sorted(CORPUS_FORMATS)})")
        self._quantized = self.cfg.corpus_format != "bf16"
        if self._quantized and self.cfg.stage1 == "local":
            raise ValueError(
                "stage1='local' routes candidates by scanning raw corpus "
                "token rows inside the shard_map and cannot serve a "
                f"{self.cfg.corpus_format!r} corpus; use stage1='host' "
                "with candidate-carrying requests")
        mesh = None
        if self.cfg.mesh_axes:
            names = tuple(a for a, _ in self.cfg.mesh_axes)
            shape = tuple(int(n) for _, n in self.cfg.mesh_axes)
            mesh = make_mesh(shape, names)
        elif self.cfg.stage1 == "local":
            raise ValueError("stage1='local' runs inside the corpus "
                             "shard_map and needs mesh_axes")
        self._routed = mesh is not None and self.cfg.stage1 == "local"
        # The unified facade (repro.retrieval.corpus): one attribute
        # surface for the single-device and mesh-resident placements; the
        # centroid router is built at shard time when shard-local stage-1
        # will consume it.
        self.corpus: Corpus = build_corpus(
            corpus_embs, corpus_mask, mesh=mesh,
            n_centroids=self.cfg.stage1_centroids if self._routed else 0,
            router_seed=self.cfg.seed,
            corpus_format=self.cfg.corpus_format)
        self.corpus_embs = self.corpus.embs
        self.corpus_mask = self.corpus.mask
        self._router_args = self.corpus.router_arrays()
        if mesh is not None:
            self._valid_docs = self.corpus.valid_docs_device()
        self.buckets = ShapeBuckets(self.cfg.token_buckets,
                                    self.cfg.cand_buckets)
        self._stage1_n = (self.cfg.stage1_candidates
                          or self.buckets.cand_buckets[0])
        self._stage1_n = self.buckets.cand_bucket(self._stage1_n)
        self._service_ema = 0.0           # observed batch service time (s)
        # Admission headroom is a LIVE callable: the batcher derives each
        # deadline-carrying request's admission deadline as
        # ``deadline_abs - headroom()`` at poll time, so a service-time EMA
        # that rises while requests queue tightens their release point
        # instead of leaving them frozen at submit-time headroom.
        self._batcher = DeadlineBatcher(self.cfg.batch_size,
                                        self.cfg.deadline_s, clock=clock,
                                        headroom=self._admission_headroom)
        self._exec: Dict[tuple, Any] = {}
        # Compile-once across threads (admit thread compiles stage-1 on a
        # cold miss while the dispatch thread compiles a step, etc.).
        self._exec_lock = threading.RLock()
        self._state_lock = threading.Lock()      # guards _service_ema
        self._rid = itertools.count()
        # Batch ORDINAL, not a raw seed: the executable folds it into the
        # key(cfg.seed) stream, so every batch (whatever its shape bucket)
        # reveals a distinct cell trajectory and the whole stream replays
        # bit-identically from the same config.
        self._batch_seed = itertools.count()
        self._bid = itertools.count()            # _Prepared idempotency key
        self._warmed = False
        self.metrics = EngineMetrics()
        # Fidelity ladder (validated eagerly even when backpressure!="degrade"
        # so a bad config fails at construction, not mid-serve).
        self._ladder = DegradeLadder(
            headrooms=tuple(self.cfg.degrade_headrooms),
            alpha_scales=tuple(self.cfg.degrade_alpha_scales),
            round_caps=tuple(self.cfg.degrade_round_caps))
        # Per-shard health (mesh engines only): the failover mask every
        # prepared batch snapshots. Mutable at runtime via fail_shard /
        # restore_shard — the compiled executables take it as a traced
        # operand, so flipping health never recompiles.
        self._health_lock = threading.Lock()
        self._healthy: Optional[np.ndarray] = None
        if mesh is not None:
            self._healthy = np.ones((self.corpus.n_shards,), bool)
            self.metrics.record_shard_health(self._healthy)

    def _admission_headroom(self) -> float:
        """Expected batch service time the batcher must leave between
        admission and the completion deadline — the LIVE estimate, floored
        by the configured static headroom."""
        with self._state_lock:
            return max(self.cfg.deadline_headroom_s, self._service_ema)

    @property
    def sharded(self) -> Optional[Corpus]:
        """The mesh-resident corpus view, None on a single-device engine
        (back-compat name; ``self.corpus`` is the unified facade)."""
        return self.corpus if self.corpus.mesh is not None else None

    # -- shard health / failover ------------------------------------------

    def shard_health(self) -> Optional[np.ndarray]:
        """Copy of the per-shard health mask (None off-mesh)."""
        if self._healthy is None:
            return None
        with self._health_lock:
            return self._healthy.copy()

    def set_shard_health(self, shard: int, healthy: bool) -> None:
        """Flip one shard's health. An unhealthy shard stops receiving
        routed quota mass (its share re-routes to the healthy shards) and
        its documents are masked out of the scorecard merge; completions
        report the resulting partial ``coverage``. Traced, not compiled:
        the health vector is an executable operand."""
        if self._healthy is None:
            raise ValueError("shard health needs a mesh-resident corpus "
                             "(set mesh_axes)")
        S = len(self._healthy)
        if not 0 <= shard < S:
            raise ValueError(f"shard {shard} out of range [0, {S})")
        with TraceAnnotation("engine.shard_health", shard=shard,
                             healthy=bool(healthy)):
            with self._health_lock:
                went_down = bool(self._healthy[shard]) and not healthy
                self._healthy[shard] = bool(healthy)
                snap = self._healthy.copy()
            if went_down:
                self.metrics.record_failover()
            self.metrics.record_shard_health(snap)

    def fail_shard(self, shard: int) -> None:
        self.set_shard_health(shard, False)

    def restore_shard(self, shard: int) -> None:
        self.set_shard_health(shard, True)

    # -- flavor policy ----------------------------------------------------

    def flavor_for(self, cand_bucket: int) -> str:
        """Dense-vs-bandit dispatch: fixed flavor, or (auto) adaptive
        reranking once the candidate bucket is large enough for the bandit's
        sublinear reveal count to beat dense scoring's fixed N*T cost."""
        if self.cfg.flavor in ("dense", "bandit"):
            return self.cfg.flavor
        if self.cfg.flavor != "auto":
            raise ValueError(f"unknown flavor {self.cfg.flavor!r}")
        return ("bandit" if cand_bucket >= self.cfg.bandit_min_candidates
                else "dense")

    # -- compilation cache ------------------------------------------------

    @property
    def compiled_buckets(self) -> List[tuple]:
        return sorted(self._exec)

    def _executable(self, key: tuple):
        """One AOT executable per bucket key; compiles (and counts) on miss.
        Thread-safe: a cold miss compiles under the executable lock, so two
        threads racing the same key produce one compile."""
        exe = self._exec.get(key)
        if exe is not None:
            return exe
        with self._exec_lock:
            return self._compile(key)

    def _compile(self, key: tuple):
        with self._exec_lock:
            exe = self._exec.get(key)
            if exe is not None:
                return exe
            with TraceAnnotation("engine.compile"):
                exe = self._build(key)
            self._exec[key] = exe
        self.metrics.record_compile(key, after_warmup=self._warmed)
        return exe

    def _build(self, key: tuple):
        """Lower + AOT-compile the executable for one bucket key (no cache
        interaction — ``_compile`` owns the cache and its lock)."""
        cfg = self.cfg
        B = cfg.batch_size
        M = self.corpus_embs.shape[2]
        if key[0] == "step":
            _, flavor, tb, nb = key
            if self.sharded is not None:
                S = self.sharded.n_shards
                step = make_sharded_serving_step(
                    self.sharded.mesh, flavor, topk=cfg.max_k,
                    corpus_format=cfg.corpus_format,
                    alpha_ef=cfg.alpha_ef, delta=cfg.delta,
                    block_docs=cfg.block_docs,
                    block_tokens=cfg.block_tokens,
                    max_rounds=cfg.max_rounds,
                    max_block_docs=cfg.max_block_docs,
                    max_block_tokens=cfg.max_block_tokens,
                    engine=cfg.bandit_engine, base_seed=cfg.seed)
                # Health mask + fidelity knobs are traced operands on the
                # ONE lowered program: failover and ladder rungs at runtime
                # never recompile, and the all-healthy/level-0 values are
                # bit-identical to the knob-less trace.
                args = (self.corpus_embs, self.corpus_mask,
                        SDS((B, tb, M), jnp.float32),
                        SDS((B, S, nb), jnp.int32),
                        SDS((B, S, nb, tb), jnp.float32),
                        SDS((B, S, nb, tb), jnp.float32),
                        SDS((S,), jnp.int32),
                        SDS((), jnp.int32),
                        SDS((S,), jnp.bool_),
                        SDS((), jnp.float32),
                        SDS((), jnp.int32))
                exe = jax.jit(step).lower(*args).compile()
            else:
                step = make_serving_step(
                    flavor, topk=cfg.max_k, alpha_ef=cfg.alpha_ef,
                    delta=cfg.delta, block_docs=cfg.block_docs,
                    block_tokens=cfg.block_tokens, max_rounds=cfg.max_rounds,
                    max_block_docs=cfg.max_block_docs,
                    max_block_tokens=cfg.max_block_tokens,
                    engine=cfg.bandit_engine)
                base = cfg.seed

                def run(ce, cm, q, cand, a, b, seed, a_s, r_c):
                    # Per-batch PRNG: fold the batch ordinal into the
                    # engine-seed stream (never key(seed + ordinal), which
                    # aliases across engines with nearby seeds).
                    k = jax.random.fold_in(jax.random.key(base), seed)
                    return step(ce, cm, q, cand, a, b, k,
                                alpha_scale=a_s, round_cap=r_c)

                args = (self.corpus_embs, self.corpus_mask,
                        SDS((B, tb, M), jnp.float32),
                        SDS((B, nb), jnp.int32),
                        SDS((B, nb, tb), jnp.float32),
                        SDS((B, nb, tb), jnp.float32),
                        SDS((), jnp.int32),
                        SDS((), jnp.float32),
                        SDS((), jnp.int32))
                exe = jax.jit(run).lower(*args).compile()
        elif key[0] == "routed":
            # One-shard_map pipeline: centroid route + shard-local stage-1
            # + rerank + merge, one executable per (flavor, token bucket)
            # — the candidate bucket is pinned to the stage-1 width.
            _, flavor, tb = key
            corpus = self.corpus
            step = make_routed_serving_step(
                corpus.mesh, flavor, topk=cfg.max_k,
                n_local=self._stage1_n, n_total=cfg.stage1_total,
                kprime=cfg.stage1_kprime, support=cfg.support,
                prereveal_ann=cfg.prereveal_ann, alpha_ef=cfg.alpha_ef,
                delta=cfg.delta, block_docs=cfg.block_docs,
                block_tokens=cfg.block_tokens, max_rounds=cfg.max_rounds,
                max_block_docs=cfg.max_block_docs,
                max_block_tokens=cfg.max_block_tokens,
                engine=cfg.bandit_engine, base_seed=cfg.seed)
            cents, mass = self._router_args
            args = (self.corpus_embs, self.corpus_mask, cents, mass,
                    SDS((B, tb, M), jnp.float32),
                    SDS((corpus.n_shards,), jnp.int32),
                    SDS((), jnp.int32),
                    SDS((corpus.n_shards,), jnp.bool_),
                    SDS((), jnp.float32),
                    SDS((), jnp.int32))
            exe = jax.jit(step).lower(*args).compile()
        elif key[0] == "stream":
            # Continuous-batching slice executable: one static shape for
            # the whole stream, per-slot PRNG keys, frontier state donated
            # (the old slice's buffers back the new slice's).
            _, tb, nb = key
            if self.sharded is not None:
                raise ValueError("continuous (slot-refill) serving is "
                                 "single-device; unset mesh_axes")
            step = make_streaming_step(
                topk=cfg.max_k, alpha_ef=cfg.alpha_ef, delta=cfg.delta,
                block_docs=cfg.block_docs, block_tokens=cfg.block_tokens,
                max_rounds=cfg.max_rounds,
                max_block_docs=cfg.max_block_docs,
                max_block_tokens=cfg.max_block_tokens,
                trip_limit=cfg.stream_trip_limit)
            kd = jax.random.key(0).dtype
            state_sds = FrontierState(
                cellvals=SDS((B * nb, tb), jnp.float32),
                stats=SDS((B * nb, 3), jnp.float32),
                key=SDS((B,), kd),
                rounds=SDS((B,), jnp.int32),
                done=SDS((B,), jnp.bool_))
            args = (self.corpus_embs, self.corpus_mask,
                    SDS((B, tb, M), jnp.float32),
                    SDS((B, nb), jnp.int32),
                    SDS((B, nb, tb), jnp.float32),
                    SDS((B, nb, tb), jnp.float32),
                    state_sds,
                    SDS((B,), jnp.bool_),
                    SDS((B,), kd))
            exe = jax.jit(step, donate_argnums=(6,)).lower(*args).compile()
        elif key[0] == "stage1":
            _, tb = key
            if self._quantized:
                raise ValueError(
                    "stage-1 ANN needs a dense corpus; quantized engines "
                    "serve candidate-carrying requests only")
            nb, kp, support = self._stage1_n, cfg.stage1_kprime, cfg.support
            if self.sharded is not None:
                stage1 = make_sharded_stage1(self.sharded.mesh, kprime=kp,
                                             max_candidates=nb,
                                             support=support)
            else:
                def stage1(ce, cm, q):
                    cs = generate_candidates_batch(ce, cm, q, kprime=kp,
                                                   max_candidates=nb,
                                                   support=support)
                    return cs.doc_ids, cs.a, cs.b

            args = (self.corpus_embs, self.corpus_mask,
                    SDS((B, tb, M), jnp.float32))
            exe = jax.jit(stage1).lower(*args).compile()
        else:
            raise KeyError(key)
        return exe

    def _autotune_dims(self) -> List[Tuple[str, Dict[str, int]]]:
        """The (op, dims) kernel shape buckets the compiled executables
        will launch — dense buckets hit ``maxsim_batch``, bandit buckets
        hit the fused reveal round (and its ``gather_maxsim`` chain-oracle
        twin, so A/B runs stay tuned too)."""
        cfg = self.cfg
        B = cfg.batch_size
        L, M = self.corpus_embs.shape[1], self.corpus_embs.shape[2]
        half = max(cfg.block_docs // 2, 1)
        G = max(cfg.block_tokens, 1)
        # Mirror ops._fmt_dims: a quantized launch keys its tuning bucket
        # with the format ordinal, so the tuned bucket IS the launched
        # bucket; bf16 adds nothing (persisted tables stay valid).
        fmt = ({} if not self._quantized
               else {"FMT": format_ordinal(cfg.corpus_format)})
        out: List[Tuple[str, Dict[str, int]]] = []
        for tb in self.buckets.token_buckets:
            for nb in self.buckets.cand_buckets:
                # Sharded or not, the per-device candidate list is nb wide
                # (route_batch packs n_local=nb slots per shard).
                if self.flavor_for(nb) == "dense":
                    out.append(("maxsim_batch",
                                dict(B=B, N=nb, T=tb, L=L, M=M, **fmt)))
                else:
                    # Frontier reveal launch geometry — MUST mirror
                    # core.frontier's width math or the tuned bucket is
                    # never the launched bucket: selection widths grow
                    # with the growth knobs (half_w docs, G_cap tokens),
                    # and the launch batch is the flat Q*W rows without
                    # doc growth or the compacted F = Q*2*half with it.
                    half_w = min(max(cfg.max_block_docs // 2, half),
                                 max(nb, 1))
                    rows = B * 2 * (half if half_w > half else half_w)
                    g = min(max(cfg.max_block_tokens, G), max(tb, 1))
                    dims = dict(B=rows, G=g, L=L, M=M, D=B * nb, TQ=B * tb,
                                **fmt)
                    out.append(("fused_reveal", dims))
                    out.append(("gather_maxsim", dims))
        return out

    def autotune(self) -> int:
        """Time candidate kernel block configurations for every shape
        bucket the serving executables will launch and record the winners
        in the tuning table (``repro.kernels.tuning``). Buckets already
        covered by a loaded table entry are skipped. Returns the number of
        buckets measured; wall time lands in ``metrics.autotune_s``."""
        t0 = time.perf_counter()
        measured = 0
        for op, dims in self._autotune_dims():
            if tuning.bucket_key(op, dims) in tuning.table():
                continue
            # Time at the corpus dtype: a bf16 corpus moves half the bytes
            # per tile, and the winning block_l can differ from f32's. A
            # quantized bucket carries its FMT dim — autotune_op encodes
            # the synthetic corpus into that format itself, so the dense
            # dtype here covers the queries (and the pre-encode source).
            dtype = (jnp.float32 if self._quantized
                     else self.corpus_embs.dtype)
            autotune_op(op, dims, dtype=dtype)
            measured += 1
        self.metrics.autotune_s += time.perf_counter() - t0
        self.metrics.autotune_buckets += measured
        return measured

    def warmup(self) -> List[tuple]:
        """Pre-compile every bucket the policy can reach; after this returns
        the engine serves any admissible stream with zero recompiles.

        When ``cfg.autotune`` is set, kernel block sizes are tuned FIRST
        (per shape bucket, reusing/persisting ``cfg.tuning_table``), so the
        AOT executables bake in the tuned tiles and the zero-recompile
        contract is untouched."""
        cfg = self.cfg
        if cfg.tuning_table and os.path.exists(cfg.tuning_table):
            self.metrics.tuning_entries_loaded += tuning.load_table(
                cfg.tuning_table)
        if cfg.autotune:
            self.autotune()
            if cfg.tuning_table:
                # Persist only THIS engine's buckets: the in-process table
                # is a shared cache across engines, and dumping it whole
                # would leak another engine's buckets into this file.
                tuning.save_table(cfg.tuning_table, keys={
                    tuning.bucket_key(op, dims)
                    for op, dims in self._autotune_dims()})
        for tb in self.buckets.token_buckets:
            if not self._quantized:
                # Stage-1 ANN traces over raw token rows; quantized engines
                # reject candidate-less requests at submit, so the bucket
                # is unreachable and compiling it would fail.
                self._executable(("stage1", tb))
            if self._routed:
                # Candidate-less batches dispatch to the one-shard_map
                # routed pipeline; the host stage-1/step executables stay
                # compiled too (mixed candidate-carrying traffic).
                self._executable(("routed", self.flavor_for(self._stage1_n),
                                  tb))
            for nb in self.buckets.cand_buckets:
                # flavor_for is a pure function of the bucket, so exactly one
                # flavor is reachable per (tb, nb) — compile just that one.
                self._executable(("step", self.flavor_for(nb), tb, nb))
        if cfg.continuous:
            self._executable(("stream", *self._stream_bucket))
        self._warmed = True
        if cfg.audit:
            self.audit()
        return self.compiled_buckets

    # -- compile-contract audit -------------------------------------------

    _HLO_DTYPES = {"bfloat16": "bf16", "float16": "f16", "float32": "f32",
                   "float64": "f64", "int8": "s8"}

    def _bucket_peak_bound(self, key: tuple) -> int:
        """Expected peak temp-buffer bound for ONE bucket, derived from its
        launch geometry and the corpus residency format (instead of the old
        engine-wide 8x-corpus blanket): the gathered candidate working set
        in resident-format bytes, the f32 reconstruction/similarity copies
        the scorers materialize, and (stage-1 only) the full-index
        similarity scan. Factors are deliberately generous — interpret-mode
        kernels materialize more than a TPU launch — but the bound now
        scales with the bucket, so a bucket that materializes another
        bucket's (or the whole corpus's) working set still trips
        ``hlo-peak-buffer``. ``cfg.audit_peak_bytes`` overrides."""
        cfg = self.cfg
        B = cfg.batch_size
        rows, L, M = self.corpus_embs.shape
        corpus_bytes = corpus_nbytes(self.corpus_embs)
        if self.corpus.mesh is not None:
            # Per-device SPMD program: shard-local corpus, shard-local temps.
            shards = max(self.corpus.n_shards, 1)
            rows //= shards
            corpus_bytes //= shards
        if key[0] == "step":
            tb, nb = key[2], key[3]
        elif key[0] == "stream":
            tb, nb = key[1], key[2]
        elif key[0] == "routed":
            tb, nb = key[2], self._stage1_n
        else:                                     # ("stage1", tb)
            tb, nb = key[1], self._stage1_n
        fmt = cfg.corpus_format
        if fmt == "bf16":
            row_bytes = L * M * self.corpus_embs.dtype.itemsize
        else:
            # int8 payload + bf16 scale plane (+ i32 centroid ids).
            row_bytes = L * M + L * (2 + (4 if fmt == "residual" else 0))
        gathered = B * nb * row_bytes             # resident-format gather
        work = B * nb * L * max(M, tb) * 4        # f32 dequant/sim copies
        if key[0] in ("stage1", "routed"):
            work += B * tb * rows * L * 4         # full-index token kNN
        return 8 * (gathered + work) + corpus_bytes + (256 << 20)

    def _audit_spec(self, key: tuple) -> AuditSpec:
        """The per-bucket compile contract ``audit()`` asserts.

        Collective budget: a mesh-resident step/routed executable may move
        exactly the scorecard merge (per-shard (scores, gids) top-K lists)
        plus two scalar-per-query psums across shards —
        ``scorecard_budget_bytes(B, S, max_k)``; candidate embeddings and
        reveal traffic must stay shard-local. Host stage-1 over a sharded
        corpus all-gathers every shard's per-token top-k' hit lists, a
        different contract from the scorecard merge, so that one key is
        unbudgeted. Everything off-mesh gets budget 0.

        Boundary residency: a bf16 corpus arms the promotion rule; a
        quantized corpus (``corpus_embs`` is a QuantTokens whose payload
        dtype is int8) arms ``hlo-int8-residency`` — the compressed payload
        must enter every executable as an s8 parameter, never widened.
        """
        cfg = self.cfg
        corpus_dtype = self._HLO_DTYPES.get(str(self.corpus_embs.dtype))
        if cfg.audit_require_bf16 and corpus_dtype != "s8":
            # Declare the contract dtype rather than the observed one: a
            # corpus already resident in f32 then trips the promotion rule
            # on its own (corpus-sized f32) entry parameters. A quantized
            # corpus is already under the stricter int8 rule.
            corpus_dtype = "bf16"
        corpus_elems = int(np.prod(self.corpus_embs.shape))
        meshed = self.corpus.mesh is not None
        if meshed:
            # Optimized HLO is per-device SPMD: entry parameters carry
            # shard-local shapes, so the promotion threshold must too.
            corpus_elems //= max(self.corpus.n_shards, 1)
        if key[0] in ("step", "routed") and meshed:
            budget = scorecard_budget_bytes(cfg.batch_size,
                                            self.corpus.n_shards, cfg.max_k)
        elif key[0] == "stage1" and meshed:
            budget = None
        else:
            budget = 0
        peak = cfg.audit_peak_bytes or self._bucket_peak_bound(key)
        return AuditSpec(collective_budget=budget, peak_bytes=peak,
                         corpus_dtype=corpus_dtype,
                         corpus_elems=corpus_elems)

    def audit(self) -> Dict[tuple, Any]:
        """Run the compile-contract auditor over every compiled bucket:
        no host callbacks / infeed / outfeed, no f64, no f32-resident
        corpus promotion (bf16 corpora), collective bytes within the
        scorecard budget, peak temp buffers bounded. Raises
        :class:`repro.analysis.hlo_audit.AuditError` with op provenance on
        the first violated contract; returns ``{bucket key: AuditReport}``
        when every executable passes."""
        with self._exec_lock:
            items = sorted(self._exec.items())
        reports: Dict[tuple, Any] = {}
        for key, exe in items:
            reports[key] = audit_executable(exe, self._audit_spec(key),
                                            label=repr(key))
        return reports

    @property
    def _stream_bucket(self) -> Tuple[int, int]:
        """Continuous mode serves every request through ONE compiled shape:
        the largest token bucket x the largest candidate bucket (any
        admissible request pads into it, so refill never recompiles)."""
        return (self.buckets.token_buckets[-1],
                max(self.buckets.cand_buckets[-1], self._stage1_n))

    # -- request lifecycle ------------------------------------------------

    def submit(self, request: Request) -> int:
        """Admit one request; returns its rid. Completions surface from
        ``poll``/``drain`` (requests are served strictly in batches).
        The caller's Request is not mutated — the engine queues its own
        copy, so one Request object may be submitted repeatedly."""
        q = np.asarray(request.query, np.float32)
        if q.ndim != 2 or q.shape[1] != self.corpus_embs.shape[2]:
            raise ValueError(f"query must be (T, {self.corpus_embs.shape[2]})")
        self.buckets.token_bucket(q.shape[0])          # validate fit
        if request.cand_ids is None and self._quantized:
            # Stage-1 ANN (retrieval.ann.generate_candidates) scans raw
            # token rows; a compressed corpus only serves the rerank path.
            raise ValueError(
                "candidate-less requests need the engine's stage-1 ANN, "
                f"which a {self.cfg.corpus_format!r} corpus cannot run — "
                "provide cand_ids or serve with corpus_format='bf16'")
        if request.cand_ids is not None:
            self.buckets.cand_bucket(len(request.cand_ids))
            cand = np.asarray(request.cand_ids)
            n_docs = (self.sharded.n_docs if self.sharded is not None
                      else self.corpus_embs.shape[0])
            if cand.size and (cand.min() < 0 or cand.max() >= n_docs):
                # Reject the one bad request HERE: a stale id surfacing
                # later (e.g. from the sharded routing table) would fail
                # mid-batch and take every batchmate down with it.
                raise ValueError(
                    f"cand_ids must lie in [0, {n_docs}); got range "
                    f"[{int(cand.min())}, {int(cand.max())}]")
        if request.k > self.cfg.max_k:
            raise ValueError(f"k={request.k} > compiled max_k={self.cfg.max_k}")
        arrival = self.clock()
        admitted = dataclasses.replace(
            request, query=q, rid=next(self._rid), arrival=arrival,
            deadline_abs=(None if request.deadline_s is None
                          else arrival + request.deadline_s))
        # Admission deadline = completion deadline - expected service time,
        # so the batch still has time to EXECUTE before the request is due.
        # The batcher derives it from ``deadline_abs`` and the engine's
        # live ``_admission_headroom()`` at every poll — never frozen here,
        # where a later EMA rise could not reach it.
        self._enqueue(admitted)
        return admitted.rid

    def _enqueue(self, admitted: Request) -> None:
        """Queue placement for a validated request (the async engine's
        continuous mode overrides this to feed the slot-refill stream)."""
        self._batcher.add(admitted, deadline_abs=admitted.deadline_abs)

    def next_expiry(self) -> Optional[float]:
        """Absolute clock time at which the pending (partial) batch will be
        released; None when the queue is empty. Drive your poll loop off
        this instead of busy-waiting."""
        return self._batcher.next_expiry()

    def poll(self) -> List[Completion]:
        """Serve at most one released batch; [] while the admission queue is
        neither full nor past its tightest deadline."""
        out = self._batcher.poll()
        if out is None:
            return []
        return self._serve_batch(*out)

    def drain(self) -> List[Completion]:
        """End of stream: serve every full batch, then flush the remainder
        (flush releases at most one padded batch per call)."""
        done: List[Completion] = []
        while True:
            out = self._batcher.poll()
            if out is None:
                break
            done.extend(self._serve_batch(*out))
        while True:
            out = self._batcher.flush()
            if out is None:
                break
            done.extend(self._serve_batch(*out))
        return done

    # -- batch execution --------------------------------------------------

    def _serve_batch(self, reqs: Sequence[Request],
                     n_real: int) -> List[Completion]:
        """Synchronous path: prepare, dispatch, and harvest back to back."""
        prep = self._prepare_batch(reqs, n_real, self.clock())
        prep, out = self._launch(prep)
        with TraceAnnotation("engine.harvest", bid=prep.bid):
            comps = self._finish_batch(prep, out)
        self.metrics.record_delivered(prep.bid, self.clock())
        return comps

    def _dispatch_batch(self, prep: _Prepared):
        """Launch the batch's executable. JAX dispatch is asynchronous:
        this returns device arrays immediately; only ``_finish_batch``
        blocks on them — the property the async pipeline overlaps on."""
        return prep.exe(*prep.args)

    def _launch(self, prep: _Prepared) -> Tuple[_Prepared, Any]:
        """``_dispatch_batch`` under its span; stamps ``t_dispatched``."""
        with TraceAnnotation("engine.dispatch", bid=prep.bid):
            out = self._dispatch_batch(prep)
        return prep._replace(t_dispatched=self.clock()), out

    def _degrade_level(self, real: Sequence[Request], flavor: str) -> int:
        """Fidelity-ladder rung for this batch: 0 unless the degrade
        policy is on, the batch has fidelity to trade (bandit flavor on a
        knob-aware reveal engine), and the tightest deadline's headroom
        ratio has fallen below the ladder thresholds."""
        cfg = self.cfg
        if (cfg.backpressure != "degrade" or flavor != "bandit"
                or cfg.bandit_engine == "vmapped"):
            return 0
        deadlines = [r.deadline_abs for r in real
                     if r.deadline_abs is not None]
        expected = self._admission_headroom()
        if not deadlines or expected <= 0:
            return 0
        ratio = (min(deadlines) - self.clock()) / expected
        return self._ladder.level_for(ratio)

    def _prepare_batch(self, reqs: Sequence[Request], n_real: int,
                       t_release: float) -> _Prepared:
        """Host-side batch assembly: bucket, pad, stage-1, route — no
        waiting on the main step executable. Assigns the batch its ``bid``
        and stamps ``t_prepared``."""
        bid = next(self._bid)
        with TraceAnnotation("engine.prepare", bid=bid):
            real = list(reqs[:n_real])
            tb = self.buckets.token_bucket(max(r.query.shape[0]
                                               for r in real))
            if self._routed and all(r.cand_ids is None for r in reqs):
                prep = self._prepare_batch_routed(reqs, real, n_real, tb,
                                                  t_release, bid)
            else:
                prep = self._prepare_batch_local(reqs, real, n_real, tb,
                                                 t_release, bid)
        return prep._replace(t_prepared=self.clock())

    def _prepare_batch_local(self, reqs: Sequence[Request],
                             real: List[Request], n_real: int, tb: int,
                             t_release: float, bid: int) -> _Prepared:
        """Batches served by the step executable: candidate lists padded
        into their bucket, stage-1 run first for requests without one."""
        cfg = self.cfg
        provided = [r.cand_ids for r in reqs]
        missing = [c is None for c in provided]
        n_need = max([len(c) for c in provided if c is not None], default=0)
        if any(missing):
            n_need = max(n_need, self._stage1_n)
        nb = self.buckets.cand_bucket(max(n_need, 1))

        queries = pad_queries([r.query for r in reqs], tb)
        cand = pad_candidates(provided, nb)
        n_toks = [r.query.shape[0] for r in reqs]
        a, b = support_bounds(cand, n_toks, tb, cfg.support)

        stage1_s = 0.0
        if any(missing):
            t0 = self.clock()
            with TraceAnnotation("engine.stage1", bid=bid):
                ids1, a1, b1 = self._executable(("stage1", tb))(
                    self.corpus_embs, self.corpus_mask, jnp.asarray(queries))
                ids1, a1, b1 = (np.asarray(ids1), np.asarray(a1),
                                np.asarray(b1))
            stage1_s = self.clock() - t0
            for i, miss in enumerate(missing):
                if miss:
                    cand[i, :self._stage1_n] = ids1[i]
                    cand[i, self._stage1_n:] = -1
                    a[i, :self._stage1_n] = a1[i]
                    a[i, self._stage1_n:] = 0.0
                    b[i, :self._stage1_n] = b1[i]
                    b[i, self._stage1_n:] = 0.0

        flavor = self.flavor_for(nb)
        exe = self._executable(("step", flavor, tb, nb))
        seed = jnp.int32(next(self._batch_seed))
        level = self._degrade_level(real, flavor)
        a_s, r_c = self._ladder.knobs(level)
        knob_args = (jnp.float32(a_s), jnp.int32(r_c))
        if self.sharded is not None:
            sc = self.sharded
            hl = self.shard_health()
            cov = self._candidate_coverage(cand, real, hl, sc.docs_per_shard)
            # One placement computation for ids + payloads; the dense
            # flavor never reads the support bounds, so skip routing them
            # and ship zeros of the compiled shape.
            payloads = () if flavor == "dense" else (a, b)
            cand_l, routed = route_batch(cand, payloads, sc.docs_per_shard,
                                         sc.n_shards, n_local=nb)
            if flavor == "dense":
                zero = np.zeros((cand.shape[0], sc.n_shards, nb, tb),
                                np.float32)
                a_l, b_l = zero, zero
            else:
                a_l, b_l = routed
            args = (self.corpus_embs, self.corpus_mask, jnp.asarray(queries),
                    jnp.asarray(cand_l), jnp.asarray(a_l), jnp.asarray(b_l),
                    self._valid_docs, seed, jnp.asarray(hl)) + knob_args
        else:
            cov = hl = None
            args = (self.corpus_embs, self.corpus_mask, jnp.asarray(queries),
                    jnp.asarray(cand), jnp.asarray(a), jnp.asarray(b),
                    seed) + knob_args
        return _Prepared(real, n_real, (tb, nb), flavor, exe, args,
                         t_release, bid, cov, level, stage1_s=stage1_s,
                         shards_healthy=None if hl is None else int(hl.sum()))

    @staticmethod
    def _candidate_coverage(cand: np.ndarray, real: Sequence[Request],
                            healthy: np.ndarray,
                            docs_per_shard: int) -> Optional[np.ndarray]:
        """Per-request fraction of its real candidates living on healthy
        shards — what the merge will actually search after the failover
        mask drops the dead shards. None (all 1.0) on a healthy mesh."""
        if healthy.all():
            return None
        cov = np.ones((len(real),), np.float32)
        for i in range(len(real)):
            ids = cand[i][cand[i] >= 0]
            if ids.size:
                cov[i] = float(np.mean(healthy[ids // docs_per_shard]))
        return cov

    def _prepare_batch_routed(self, reqs: Sequence[Request],
                              real: List[Request], n_real: int, tb: int,
                              t_release: float, bid: int) -> _Prepared:
        """One-shard_map dispatch for candidate-less batches on a routed
        engine: no host stage-1, no routing tables — queries in,
        scorecards out."""
        nb = self._stage1_n
        flavor = self.flavor_for(nb)
        exe = self._executable(("routed", flavor, tb))
        queries = pad_queries([r.query for r in reqs], tb)
        seed = jnp.int32(next(self._batch_seed))
        cents, mass = self._router_args
        level = self._degrade_level(real, flavor)
        a_s, r_c = self._ladder.knobs(level)
        hl = self.shard_health()
        cov = None
        if not hl.all():
            # Candidates are chosen inside the shard_map — the searchable
            # universe is the healthy shards' document mass.
            vd = np.asarray(self.corpus.valid_docs, np.float64)
            cov = np.full((len(real),),
                          float(vd[hl].sum() / max(vd.sum(), 1.0)),
                          np.float32)
        args = (self.corpus_embs, self.corpus_mask, cents, mass,
                jnp.asarray(queries), self._valid_docs, seed,
                jnp.asarray(hl), jnp.float32(a_s), jnp.int32(r_c))
        return _Prepared(real, n_real, (tb, nb), flavor, exe, args,
                         t_release, bid, cov, level,
                         shards_healthy=int(hl.sum()))

    def _finish_batch(self, prep: _Prepared, out) -> List[Completion]:
        """Completion harvest: the ONLY stage that blocks on the device."""
        with TraceAnnotation("engine.harvest.wait", bid=prep.bid):
            out = jax.block_until_ready(out)
        t_ready = self.clock()
        with TraceAnnotation("engine.harvest.copy", bid=prep.bid):
            return self._completions(prep, out, t_ready)

    def _observe_service(self, service_s: float) -> None:
        """Fold one batch's service time into the admission EMA."""
        with self._state_lock:
            self._service_ema = (service_s if not self.metrics.batches
                                 else 0.7 * self._service_ema
                                 + 0.3 * service_s)

    def _completions(self, prep: _Prepared, out,
                     t_ready: float) -> List[Completion]:
        """Copy a finished batch to the host; record it and build its
        completions."""
        cfg = self.cfg
        real, n_real = prep.real, prep.n_real
        bucket, flavor, t_release = prep.bucket, prep.flavor, prep.t_release
        scores, gids, frac, stats = (np.asarray(x) for x in out)
        t_done = self.clock()

        shard_quota = None
        if stats.ndim == 2:        # sharded: per-shard diagnostic vectors
            shard_occ = tuple(float(x) for x in stats[:, 0])
            shard_rounds = tuple(float(x) for x in stats[:, 1])
            if stats.shape[1] >= 5:   # routed step: quota-share columns
                shard_quota = tuple(float(x) for x in stats[:, 3])
            # aggregate occupancy over the shards that did frontier work
            busy = stats[stats[:, 1] > 0]
            agg = (float(np.mean(busy[:, 0])) if len(busy)
                   else float(np.mean(stats[:, 0])),
                   float(np.sum(stats[:, 1])), float(np.sum(stats[:, 2])))
            quarantined = float(np.sum(stats[:, -1]))
        else:
            shard_occ = shard_rounds = None
            agg = (float(stats[0]), float(stats[1]), float(stats[2]))
            quarantined = float(stats[3])

        self._observe_service(t_done - t_release)
        record = BatchRecord(
            bucket=bucket, flavor=flavor, n_real=n_real,
            occupancy=n_real / cfg.batch_size,
            reveal_fraction=float(np.mean(frac[:n_real])),
            frontier_occupancy=agg[0],
            total_rounds=agg[1],
            lockstep_waste=agg[2],
            shard_occupancy=shard_occ,
            shard_rounds=shard_rounds,
            shard_quota_share=shard_quota,
            shards_healthy=prep.shards_healthy,
            quarantined=quarantined,
            degrade_level=prep.degrade_level,
            bid=prep.bid, t_release=t_release, t_prepared=prep.t_prepared,
            stage1_s=prep.stage1_s, t_dispatched=prep.t_dispatched,
            t_ready=t_ready, t_done=t_done)

        done: List[Completion] = []
        for i, r in enumerate(real):
            latency = t_done - r.arrival
            comp = Completion(
                rid=r.rid,
                topk_ids=gids[i, :r.k].copy(),
                topk_scores=scores[i, :r.k].copy(),
                queue_wait_s=t_release - r.arrival,
                latency_s=latency,
                # Serve-time stamping against the ABSOLUTE deadline captured
                # at admission: however the request reached this batch
                # (deadline release, full-batch release, drain, or a poll
                # that raced a fresh admission past a stale next_expiry()),
                # finishing after the deadline is a miss.
                deadline_miss=(r.deadline_abs is not None
                               and t_done > r.deadline_abs + 1e-9),
                flavor=flavor, bucket=bucket,
                reveal_fraction=float(frac[i]),
                coverage=(float(prep.coverage[i])
                          if prep.coverage is not None else 1.0)
                         * r.coverage_scale,
                degrade_level=prep.degrade_level, bid=prep.bid)
            done.append(comp)
        self.metrics.record_batch(record, done)
        return done


# Dispatch-queue sentinel: the admit thread pushes it when it exits so the
# dispatch thread drains its in-flight batches and terminates.
_STOP = object()


# -- static thread-safety contract (repro.analysis.locks) --------------------
# The lockset linter roots one attribute-access set per thread type at these
# methods (closing over ``self.*`` method references) and fails any attribute
# shared by >= 2 thread types that is neither in GUARDED_BY nor consistently
# accessed under one ``with self.<lock>:``.
THREAD_ENTRY_POINTS = {
    "caller": ("submit", "poll", "drain", "stop", "start", "warmup",
               "future", "next_expiry", "autotune", "audit",
               "set_shard_health", "fail_shard", "restore_shard",
               "shard_health"),
    "admit": ("_admit_loop", "_guard"),
    "dispatch": ("_dispatch_loop", "_guard"),
    "stream": ("_stream_loop", "_guard"),
    "supervisor": ("_pre_restart", "_supervision_exhausted", "_spawn"),
}

# Attribute -> its guard. A lock name ("_done_cv", "_exec_lock", ...) is
# VERIFIED: every write outside __init__ must sit under ``with self.<lock>``.
# The mode strings document guards the linter cannot check lexically:
#   internal — the object locks itself (DeadlineBatcher, EngineMetrics);
#   atomic   — single CPython-atomic pointer swap, readers tolerate either
#              value (the supervisor handle);
#   ordered  — writes happen-before the reading thread starts (start()'s
#              thread bookkeeping, supervisor-callback state mutated only
#              while the watched thread is dead) or after it joins, or
#              both fall in one garbage collection, which the interpreter
#              runs on one thread at a time (the open gc span);
#   init     — written once before any serving thread exists (warmup flag).
GUARDED_BY = {
    "_futures": "_done_cv",
    "_submitted": "_done_cv",
    "_finished": "_done_cv",
    "_thread_exc": "_done_cv",
    "_completed": "_completed_lock",
    "_delivered_rids": "_completed_lock",
    "_disp_inflight": "_inflight_lock",
    "_inflight": "_inflight_lock",
    "_stream_q": "_work_cv",
    "_service_ema": "_state_lock",
    "_healthy": "_health_lock",
    "_exec": "_exec_lock",
    "_batcher": "internal",
    "_supervisor": "atomic",
    "_admit_holding": "ordered",
    "_gc_trace": "ordered",
    "_harvested": "ordered",
    "_stream_slots": "ordered",
    "_targets": "ordered",
    "_thread_by_name": "ordered",
    "_threads": "ordered",
    "_started": "ordered",
    "_warmed": "init",
}


class AsyncRetrievalEngine(RetrievalEngine):
    """Async continuous-serving runtime over the same compiled buckets.

    Two dedicated threads split the synchronous engine's serve loop the way
    an offline-inference pipeline does:

    * the ADMIT thread drives the deadline batcher (sleeping toward
      ``next_expiry`` — which wakes immediately on a ready full batch) and
      runs host-side batch preparation (bucketing, padding, stage-1,
      routing);
    * the DISPATCH thread launches prepared batches on the device and,
      because JAX dispatch is asynchronous, immediately accepts the next
      one — batch i+1 dispatches while i executes. It calls
      ``jax.block_until_ready`` only at completion-harvest time, once the
      pipeline holds ``cfg.pipeline_depth`` batches (or goes idle).

    Admission backpressure (``cfg.backpressure``) rejects or degrades a
    deadline-carrying request at ``submit`` when the projected completion
    — queue backlog plus pipeline depth, costed at the live service-time
    EMA — already overruns its deadline.

    With ``cfg.continuous`` the batch pipeline is replaced by slot-level
    continuous batching: ONE resumable streaming executable
    (``retrieval.service.make_streaming_step``) holds a ``batch_size``-slot
    frontier; every device dispatch advances all live slots
    ``cfg.stream_trip_limit`` reveal rounds, and slots whose query retired
    are harvested and refilled from the admission queue mid-flight —
    the whole batch never drains to admit new work.

    Completions surface three ways: ``poll()`` (non-blocking pop of
    everything finished since the last poll), ``drain()`` (block until all
    submitted work completes), and per-request ``future(rid)``. The
    synchronous engine remains the parity oracle: an un-``start()``-ed
    async engine serves exactly like :class:`RetrievalEngine`.
    """

    def __init__(self, corpus_embs, corpus_mask,
                 config: Optional[EngineConfig] = None, *,
                 clock: Callable[[], float] = time.monotonic,
                 poll_interval_s: float = 0.002,
                 fault_plan: Optional[FaultPlan] = None):
        super().__init__(corpus_embs, corpus_mask, config, clock=clock)
        if self.cfg.backpressure not in ("none", "reject", "degrade"):
            raise ValueError(f"unknown backpressure policy "
                             f"{self.cfg.backpressure!r}")
        if self.cfg.pipeline_depth < 1:
            raise ValueError("pipeline_depth must be >= 1")
        self._poll_interval = float(poll_interval_s)
        self._work_cv = threading.Condition()
        self._done_cv = threading.Condition()
        self._stop_evt = threading.Event()
        self._drain_evt = threading.Event()
        self._prep_q: queue_mod.Queue = queue_mod.Queue(
            maxsize=self.cfg.pipeline_depth)
        self._completed_lock = threading.Lock()
        self._completed: deque = deque()
        self._futures: Dict[int, Future] = {}
        self._submitted = 0
        self._finished = 0
        self._inflight = 0
        self._stream_q: deque = deque()
        self._threads: List[threading.Thread] = []
        self._thread_exc: Optional[BaseException] = None
        self._started = False
        # Fault-injection harness: an inert/None plan adds nothing to the
        # serving loops (the chaos hook returns before ticking).
        self._fault_plan = (fault_plan if fault_plan is not None
                            and not fault_plan.empty else None)
        # Supervised-restart state. Every piece of in-flight pipeline work
        # lives on the ENGINE so a restarted thread resumes it: the batch
        # the admit thread is offering to a full dispatch queue
        # (_admit_holding), the dispatched-batch deque (_disp_inflight),
        # and the continuous stream's occupied slots (_stream_slots).
        # Harvest idempotency comes from _harvested (batch bids finished)
        # plus rid-dedup at delivery (_delivered_rids) — together they
        # give the zero-lost / zero-duplicated completion guarantee.
        self._supervisor: Optional[Supervisor] = None
        self._targets: Dict[str, Callable[[], None]] = {}
        self._thread_by_name: Dict[str, threading.Thread] = {}
        self._inflight_lock = threading.Lock()
        self._disp_inflight: deque = deque()
        self._admit_holding: Optional[_Prepared] = None
        self._harvested: set = set()
        self._delivered_rids: set = set()
        self._stream_slots: List[Optional[Request]] = []
        # The open ``engine.gc`` span of the collection in progress.
        self._gc_trace: Optional[TraceAnnotation] = None

    # -- lifecycle --------------------------------------------------------

    def start(self) -> "AsyncRetrievalEngine":
        """Spawn the serving threads (plus the supervision watchdog under
        ``cfg.supervise``). Idempotent while running."""
        if self._started:
            return self
        self._raise_if_failed()
        self._stop_evt.clear()
        if self.cfg.continuous:
            self._targets = {"repro-stream": self._stream_loop}
        else:
            self._targets = {"repro-admit": self._admit_loop,
                             "repro-dispatch": self._dispatch_loop}
        self._thread_by_name = {}
        self._started = True
        gc.callbacks.append(self._gc_span)
        if self.cfg.supervise:
            self._supervisor = Supervisor(
                max_restarts=self.cfg.max_thread_restarts,
                interval_s=self.cfg.supervise_interval_s,
                stopping=self._stop_evt.is_set,
                on_exhausted=self._supervision_exhausted)
        for name in self._targets:
            t = self._spawn(name)
            if self._supervisor is not None:
                self._supervisor.watch(
                    name, t, factory=functools.partial(self._spawn, name),
                    on_restart=functools.partial(self._pre_restart, name))
        self._threads = list(self._thread_by_name.values())
        if self._supervisor is not None:
            self._supervisor.start()
        return self

    def _spawn(self, name: str) -> threading.Thread:
        """Build AND start one named serving thread — the initial spawn
        and the supervisor's restart factory."""
        t = threading.Thread(target=self._guard,
                             args=(self._targets[name], name), name=name,
                             daemon=True)
        self._thread_by_name[name] = t
        t.start()
        return t

    def _pre_restart(self, name: str) -> None:
        """Watchdog callback just before a dead thread is replaced."""
        self.metrics.record_restart(name)
        if name == "repro-stream":
            # The stream loop's frontier state died with its thread: the
            # occupied slots' bandit progress is unrecoverable, so fail
            # those requests LOUDLY (queued requests replay fine — the
            # fresh thread refills from the intact admission queue).
            self._fail_stream_slots(
                "continuous-stream thread restarted; in-flight slot lost")

    def _supervision_exhausted(self, name: str,
                               exc: Optional[BaseException]) -> None:
        """Restart budget spent: escalate to the unsupervised engine's
        loud thread-death failure."""
        with self._done_cv:
            self._thread_exc = exc if exc is not None else RuntimeError(
                f"{name} died with its restart budget exhausted")
            self._stop_evt.set()
            self._done_cv.notify_all()

    def stop(self) -> None:
        """Stop the serving threads, then FLUSH: every admitted request is
        completed (queued and in-flight batches are served synchronously)
        or — when serving is impossible, e.g. a dead thread — failed
        loudly with an ``error`` completion. Nothing is silently dropped
        and no future dangles after stop."""
        if not self._started:
            return
        self._stop_evt.set()
        if self._supervisor is not None:
            self._supervisor.stop()
            self._supervisor = None
        with self._work_cv:
            self._work_cv.notify_all()
        for t in list(self._thread_by_name.values()):
            t.join(timeout=60.0)
        self._started = False
        gc.callbacks.remove(self._gc_span)
        if self._thread_exc is None:
            self._shutdown_flush()
        self._fail_pending("engine stopped before serving this request")
        self._raise_if_failed()

    def _gc_span(self, phase: str, info: Dict[str, int]) -> None:
        """``gc.callbacks`` hook while started: one ``engine.gc`` span per
        collection, on whichever thread collects."""
        if phase == "start":
            self._gc_trace = TraceAnnotation("engine.gc",
                                             generation=info["generation"])
            self._gc_trace.__enter__()
        elif self._gc_trace is not None:
            self._gc_trace.__exit__(None, None, None)
            self._gc_trace = None

    def __enter__(self) -> "AsyncRetrievalEngine":
        return self.start()

    def __exit__(self, exc_type, exc, tb) -> None:
        self.stop()

    def _guard(self, fn, name: str = "") -> None:
        try:
            fn()
        except BaseException as e:
            if self._supervisor is not None and not self._stop_evt.is_set():
                # Supervised: die quietly — the watchdog restarts within
                # budget or escalates through _supervision_exhausted.
                self._supervisor.note_failure(name, e)
                return
            # Unsupervised (or stopping): propagate to drain()/stop().
            with self._done_cv:
                self._thread_exc = e
                self._stop_evt.set()
                self._done_cv.notify_all()

    def _raise_if_failed(self) -> None:
        with self._done_cv:
            exc, self._thread_exc = self._thread_exc, None
        if exc is not None:
            raise RuntimeError("serving thread died") from exc

    # -- fault injection ---------------------------------------------------

    def _chaos(self, point: str) -> None:
        """Tick the fault plan's chaos point (once per thread-loop
        iteration). Kills raise AFTER state flips apply, matching
        FaultPlan.tick's ordering."""
        plan = self._fault_plan
        if plan is None:
            return
        for f in plan.tick(point):
            if f.action == "kill":
                raise ChaosKill(f"injected kill at {point!r} "
                                f"tick {f.at}")
            if f.action == "shard_down":
                self.fail_shard(int(f.arg))
            elif f.action == "shard_up":
                self.restore_shard(int(f.arg))
            elif f.action == "delay":
                apply_delay(self.clock, float(f.arg))

    # -- admission --------------------------------------------------------

    def _backlog_batches(self) -> int:
        """Batches queued ahead of a request admitted right now."""
        B = self.cfg.batch_size
        if self.cfg.continuous:
            with self._work_cv:
                return (len(self._stream_q) + B - 1) // B
        queued = (len(self._batcher) + B - 1) // B
        with self._inflight_lock:
            inflight = self._inflight
        return queued + self._prep_q.qsize() + inflight

    def submit(self, request: Request) -> int:
        if self.cfg.continuous and not self._started:
            raise RuntimeError("continuous mode serves from the stream "
                               "thread; call start() before submit()")
        self._raise_if_failed()
        cfg = self.cfg
        if cfg.backpressure != "none" and request.deadline_s is not None:
            # Projected completion: every batch ahead of this request plus
            # its own, costed at the live expected batch service time.
            expected = self._admission_headroom()
            wait = (self._backlog_batches() + 1) * expected
            if wait > request.deadline_s:
                if cfg.backpressure == "reject":
                    self.metrics.record_rejected()
                    raise AdmissionRejected(
                        f"projected wait {wait * 1e3:.1f} ms exceeds "
                        f"deadline {request.deadline_s * 1e3:.1f} ms")
                min_nb = self.buckets.cand_buckets[0]
                if (request.cand_ids is not None
                        and len(request.cand_ids) > min_nb):
                    # First ladder rung: truncate to the cheapest compiled
                    # candidate bucket; the lost tail is a visible coverage
                    # deficit on the completion, not a silent downgrade.
                    request = dataclasses.replace(
                        request,
                        cand_ids=np.asarray(request.cand_ids)[:min_nb],
                        coverage_scale=(request.coverage_scale
                                        * min_nb / len(request.cand_ids)))
                    self.metrics.record_degraded()
        return super().submit(request)

    def _enqueue(self, admitted: Request) -> None:
        with self._done_cv:
            self._futures[admitted.rid] = Future()
            self._submitted += 1
        if self.cfg.continuous:
            with self._work_cv:
                self._stream_q.append(admitted)
                self._work_cv.notify_all()
        else:
            super()._enqueue(admitted)
            with self._work_cv:
                self._work_cv.notify_all()

    def future(self, rid: int) -> Optional[Future]:
        """The request's completion future (None for unknown rids)."""
        with self._done_cv:
            return self._futures.get(rid)

    # -- completion surfaces ----------------------------------------------

    def _resolve(self, comps: Sequence[Completion]) -> None:
        if not comps:
            return
        with self._done_cv:
            for c in comps:
                fut = self._futures.get(c.rid)
                if fut is not None and not fut.done():
                    fut.set_result(c)
                self._finished += 1
            self._done_cv.notify_all()

    def _deliver(self, comps: Sequence[Completion]) -> None:
        """Idempotent completion delivery: a rid is surfaced exactly once,
        however many times a supervised restart re-harvests its batch."""
        if not comps:
            return
        with self._completed_lock:
            fresh = [c for c in comps if c.rid not in self._delivered_rids]
            self._delivered_rids.update(c.rid for c in fresh)
        if not fresh:
            return
        self._resolve(fresh)
        with self._completed_lock:
            self._completed.extend(fresh)

    def _deliver_batch(self, bid: int, comps: Sequence[Completion]) -> None:
        """``_deliver`` one batch's completions; stamps its
        ``t_delivered``."""
        with TraceAnnotation("engine.deliver", bid=bid):
            self._deliver(comps)
        self.metrics.record_delivered(bid, self.clock())

    def poll(self) -> List[Completion]:
        """Un-started: serve synchronously (parity-oracle mode). Started:
        non-blocking pop of everything completed since the last poll.
        After stop() the completed backlog (including the shutdown flush's
        work) is still surfaced before falling back to the sync path."""
        if self._started:
            self._raise_if_failed()
        with self._completed_lock:
            out = list(self._completed)
            self._completed.clear()
        if not self._started:
            comps = super().poll()
            self._resolve(comps)
            out.extend(comps)
        return out

    def drain(self) -> List[Completion]:
        """Block until every submitted request has completed; returns the
        completions not yet surfaced through ``poll``."""
        if not self._started:
            comps = super().drain()
            self._resolve(comps)
            return comps
        self._drain_evt.set()
        with self._work_cv:
            self._work_cv.notify_all()
        try:
            with self._done_cv:
                while self._finished < self._submitted:
                    if self._thread_exc is not None or (
                            self._stop_evt.is_set()):
                        break
                    self._done_cv.wait(timeout=self._poll_interval * 5)
        finally:
            self._drain_evt.clear()
        self._raise_if_failed()
        with self._done_cv:
            if self._finished < self._submitted:
                raise RuntimeError("drain() interrupted by stop()")
        return self.poll()

    # -- batch-pipeline threads -------------------------------------------

    def _admit_loop(self) -> None:
        """Drive the deadline batcher; prepare released batches; feed the
        bounded dispatch queue (whose ``put`` blocking IS the pipeline's
        backpressure on admission work). A prepared batch is parked on
        ``_admit_holding`` until the queue accepts it, so a thread death
        mid-offer hands the batch to the restarted thread (or the stop
        flush) instead of dropping it."""
        while True:
            self._chaos("admit")
            prep = self._admit_holding
            if prep is None:
                out = self._batcher.poll()
                if out is None and self._drain_evt.is_set():
                    out = self._batcher.flush()
                if out is not None:
                    prep = self._prepare_batch(out[0], out[1], self.clock())
            if prep is not None:
                self._admit_holding = prep
                with TraceAnnotation("engine.offer", bid=prep.bid):
                    while True:
                        try:
                            self._prep_q.put(prep, timeout=0.1)
                            self._admit_holding = None
                            break
                        except queue_mod.Full:
                            if self._stop_evt.is_set():
                                # still holding: the stop flush serves it
                                self._put_stop()
                                return
                continue
            if self._stop_evt.is_set():
                self._put_stop()
                return
            with self._work_cv:
                exp = self._batcher.next_expiry()
                now = self.clock()
                tmo = (self._poll_interval if exp is None
                       else min(max(exp - now, 0.0), self._poll_interval))
                if tmo > 0:
                    with TraceAnnotation("engine.admit.idle"):
                        self._work_cv.wait(timeout=tmo)

    def _put_stop(self) -> None:
        """Best-effort dispatch sentinel: never block on a full queue (the
        dispatcher may be dead — the legacy blocking put deadlocked the
        admit thread there). A dropped sentinel is safe: the dispatcher
        also exits on stop_evt once idle, and the stop flush serves
        whatever never got dispatched and discards stray sentinels."""
        try:
            self._prep_q.put_nowait(_STOP)
        except queue_mod.Full:
            pass

    def _harvest_head(self) -> bool:
        """Finish-and-deliver the OLDEST in-flight batch, exactly once.

        Peek-finish-pop (never pop-then-finish): the batch stays on the
        engine-owned deque until its completions are delivered, so a
        thread dying inside ``_finish_batch`` leaves it for the restarted
        thread. The ``bid`` guard skips a head whose predecessor died in
        the window between delivering and popping; rid-dedup in
        ``_deliver`` backstops the symmetric window."""
        with self._inflight_lock:
            if not self._disp_inflight:
                return False
            p, o = self._disp_inflight[0]
        if p.bid not in self._harvested:
            with TraceAnnotation("engine.harvest", bid=p.bid):
                comps = self._finish_batch(p, o)
                self._harvested.add(p.bid)
                self._deliver_batch(p.bid, comps)
        with self._inflight_lock:
            if self._disp_inflight and self._disp_inflight[0][0].bid == p.bid:
                self._disp_inflight.popleft()
            self._inflight = len(self._disp_inflight)
        return True

    def _dispatch_loop(self) -> None:
        """Launch prepared batches; keep up to ``pipeline_depth`` in
        flight; block on device results only when the pipeline is full or
        idle — the JetStream-style dispatch/harvest split. In-flight
        batches live on ``self._disp_inflight`` (not the thread stack) so
        supervision restarts lose nothing."""
        depth = self.cfg.pipeline_depth
        while True:
            self._chaos("dispatch")
            with TraceAnnotation("engine.dispatch.idle"):
                try:
                    prep = self._prep_q.get(timeout=self._poll_interval)
                except queue_mod.Empty:
                    prep = None
            if prep is _STOP:
                while self._harvest_head():
                    pass
                return
            if prep is not None:
                with self._inflight_lock:
                    self._disp_inflight.append(self._launch(prep))
                    self._inflight = len(self._disp_inflight)
                    full = len(self._disp_inflight) >= depth
                if full:
                    self._harvest_head()
            elif not self._harvest_head() and self._stop_evt.is_set():
                # Restarted after the _STOP sentinel was already consumed
                # (or a racing shutdown): nothing in flight, nothing
                # queued — the stop flush owns whatever is left.
                return

    # -- shutdown flush / loud failure ------------------------------------

    def _shutdown_flush(self) -> None:
        """Serve every batch the stopped pipeline left behind, on the
        caller's thread: dispatched-but-unharvested batches, the admit
        thread's parked offer, queued prepared batches, and the admission
        queue's remainder. After this only never-admitted rids can be
        pending (there are none on a healthy stop)."""
        while self._harvest_head():
            pass
        leftovers: List[_Prepared] = []
        if self._admit_holding is not None:
            leftovers.append(self._admit_holding)
            self._admit_holding = None
        while True:
            try:
                prep = self._prep_q.get_nowait()
            except queue_mod.Empty:
                break
            if prep is not _STOP:
                leftovers.append(prep)
        for prep in leftovers:
            if prep.bid in self._harvested:
                continue
            prep, out = self._launch(prep)
            with TraceAnnotation("engine.harvest", bid=prep.bid):
                comps = self._finish_batch(prep, out)
                self._harvested.add(prep.bid)
                self._deliver_batch(prep.bid, comps)
        while True:
            out = self._batcher.poll() or self._batcher.flush()
            if out is None:
                break
            prep, out = self._launch(
                self._prepare_batch(out[0], out[1], self.clock()))
            with TraceAnnotation("engine.harvest", bid=prep.bid):
                self._deliver_batch(prep.bid, self._finish_batch(prep, out))

    def _error_completion(self, rid: int, reason: str,
                          k: Optional[int] = None) -> Completion:
        k = self.cfg.max_k if k is None else k
        return Completion(
            rid=rid, topk_ids=np.full((k,), -1, np.int32),
            topk_scores=np.full((k,), -np.inf, np.float32),
            queue_wait_s=0.0, latency_s=0.0, deadline_miss=True,
            flavor="error", bucket=(0, 0), reveal_fraction=0.0,
            coverage=0.0, error=reason)

    def _fail_pending(self, reason: str) -> None:
        """Resolve every still-pending future with a LOUD error completion
        — the zero-lost guarantee's last line: after stop() no submitted
        rid is unaccounted for and no future dangles."""
        with self._done_cv:
            pending = sorted(rid for rid, f in self._futures.items()
                             if not f.done())
        if pending:
            self._deliver([self._error_completion(rid, reason)
                           for rid in pending])

    def _fail_stream_slots(self, reason: str) -> None:
        """Fail the continuous stream's occupied slots (their on-device
        frontier state died with the stream thread)."""
        slots = self._stream_slots
        comps = []
        for s, r in enumerate(slots):
            if r is not None:
                comps.append(self._error_completion(r.rid, reason, k=r.k))
                slots[s] = None
        self._deliver(comps)

    # -- continuous (slot-refill) thread ----------------------------------

    def _stream_loop(self) -> None:
        """Slot-level continuous batching: one resumable frontier of
        ``batch_size`` slots; retired slots are harvested and refilled
        from the admission queue between slices while the other slots'
        bandit state carries forward on the device."""
        cfg = self.cfg
        B = cfg.batch_size
        tb, nb = self._stream_bucket
        exe = self._executable(("stream", tb, nb))
        M = self.corpus_embs.shape[2]
        base_key = jax.random.key(cfg.seed)
        state = init_stream_state(B, nb, tb)
        keys = jax.random.split(base_key, B)
        slot: List[Optional[Request]] = [None] * B
        # Engine-visible alias: a supervised restart fails the occupied
        # slots loudly (their frontier state died with this thread).
        self._stream_slots = slot
        slot_fill = [0.0] * B
        queries = np.zeros((B, tb, M), np.float32)
        cand = np.full((B, nb), -1, np.int32)
        a_np = np.zeros((B, nb, tb), np.float32)
        b_np = np.zeros((B, nb, tb), np.float32)

        while True:
            self._chaos("stream")
            # 1. Refill retired slots from the admission queue.
            newly: List[int] = []
            for s in range(B):
                if slot[s] is not None:
                    continue
                with self._work_cv:
                    r = (self._stream_q.popleft() if self._stream_q
                         else None)
                if r is None:
                    break
                slot[s] = r
                slot_fill[s] = self.clock()
                newly.append(s)
            fresh = np.zeros((B,), bool)
            stage1_s = 0.0
            if newly:
                need = [s for s in newly if slot[s].cand_ids is None]
                if need:
                    q_pad = np.zeros((B, tb, M), np.float32)
                    for s in need:
                        q = slot[s].query
                        q_pad[s, :q.shape[0]] = q
                    t1 = self.clock()
                    with TraceAnnotation("engine.stage1"):
                        ids1, a1, b1 = self._executable(("stage1", tb))(
                            self.corpus_embs, self.corpus_mask,
                            jnp.asarray(q_pad))
                        ids1, a1, b1 = (np.asarray(ids1), np.asarray(a1),
                                        np.asarray(b1))
                    stage1_s = self.clock() - t1
                for s in newly:
                    r = slot[s]
                    queries[s] = 0.0
                    queries[s, :r.query.shape[0]] = r.query
                    if r.cand_ids is None:
                        cand[s] = -1
                        cand[s, :self._stage1_n] = ids1[s]
                        a_np[s] = 0.0
                        b_np[s] = 0.0
                        a_np[s, :self._stage1_n] = a1[s]
                        b_np[s, :self._stage1_n] = b1[s]
                    else:
                        row = pad_candidates([r.cand_ids], nb)
                        cand[s] = row[0]
                        aa, bb = support_bounds(row, [r.query.shape[0]],
                                                tb, cfg.support)
                        a_np[s], b_np[s] = aa[0], bb[0]
                    keys = keys.at[s].set(
                        jax.random.fold_in(base_key, r.rid))
                    fresh[s] = True

            live = [s for s in range(B) if slot[s] is not None]
            if not live:
                if self._stop_evt.is_set():
                    return
                with self._work_cv:
                    if not self._stream_q:
                        self._work_cv.wait(timeout=self._poll_interval)
                continue

            # 2. One slice: every live slot advances trip_limit rounds.
            bid = next(self._bid)
            t0 = self.clock()
            with TraceAnnotation("engine.dispatch", bid=bid):
                scores, gids, frac, stats, harvest, state = exe(
                    self.corpus_embs, self.corpus_mask, jnp.asarray(queries),
                    jnp.asarray(cand), jnp.asarray(a_np), jnp.asarray(b_np),
                    state, jnp.asarray(fresh), keys)
            t_dispatched = self.clock()
            with TraceAnnotation("engine.harvest.wait", bid=bid):
                scores, gids, frac, stats, harvest = jax.block_until_ready(
                    (scores, gids, frac, stats, harvest))
            t_done = self.clock()
            scores, gids, frac, stats, harvest = (
                np.asarray(scores), np.asarray(gids), np.asarray(frac),
                np.asarray(stats), np.asarray(harvest))

            # 3. Harvest retired slots.
            comps: List[Completion] = []
            for s in live:
                if not harvest[s]:
                    continue
                r = slot[s]
                comps.append(Completion(
                    rid=r.rid,
                    topk_ids=gids[s, :r.k].copy(),
                    topk_scores=scores[s, :r.k].copy(),
                    queue_wait_s=slot_fill[s] - r.arrival,
                    latency_s=t_done - r.arrival,
                    deadline_miss=(r.deadline_abs is not None
                                   and t_done > r.deadline_abs + 1e-9),
                    flavor="bandit", bucket=(tb, nb),
                    reveal_fraction=float(frac[s]),
                    coverage=r.coverage_scale, bid=bid))
                slot[s] = None
            self._observe_service(t_done - t0)
            self.metrics.record_batch(BatchRecord(
                bucket=(tb, nb), flavor="bandit", n_real=len(live),
                occupancy=len(live) / B,
                reveal_fraction=float(np.mean(frac[live])),
                frontier_occupancy=float(stats[0]),
                total_rounds=float(stats[1]),
                lockstep_waste=float(stats[2]),
                quarantined=float(stats[3]),
                bid=bid, t_release=t0, t_prepared=t0, stage1_s=stage1_s,
                t_dispatched=t_dispatched, t_ready=t_done,
                t_done=t_done), comps)
            self._deliver_batch(bid, comps)
