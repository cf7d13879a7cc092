"""Unified corpus facade: one object for the host and mesh-resident views.

Historically the stage-1 kNN (``retrieval/ann.py`` over a single-host
``TokenIndex``) and the sharded serving path (``retrieval/sharded.py`` +
``service.py``) were two architectures glued by host-side routing tables.
This module is the seam that unifies them:

* :func:`gather_tokens` — THE candidate-embedding gather. Rank-general
  (works for a (N,) id vector or a (B, N) batch), -1 ids come back fully
  masked. ``TokenIndex.gather_docs`` and ``service.gather_candidates``
  both delegate here, so every flavor agrees on pad semantics.
* :class:`CentroidRouter` / :func:`build_router` — the IVF-style centroid
  router (ColBERTv2/PLAID direction): k-means over doc-pooled embeddings
  at corpus-build time, plus the per-(centroid, shard) doc-mass table.
  At query time :func:`route_mass` turns query-token/centroid affinities
  into per-shard candidate mass and :func:`route_quotas` converts the mass
  into integer per-shard candidate quotas that ALWAYS sum to the global
  budget (largest-remainder rounding, deterministic tie-break) — the
  skew-aware replacement for worst-case-uniform ``N_loc`` provisioning.
* :class:`Corpus` / :func:`build_corpus` — the facade object the serving
  engine holds: a single-device corpus (``mesh=None``) and a mesh-resident
  ``ShardedCorpus`` expose the same attribute surface (``embs``, ``mask``,
  ``n_shards``, ``docs_per_shard``, ``valid_docs``, ``router``, ...).

Loud-failure contract: quotas are never silently clamped. The host-side
:meth:`CentroidRouter.route` raises ``ValueError`` when a routed quota
exceeds a shard's ``valid_docs`` (or the compiled ``n_local`` capacity).
The in-shard_map path needs no clamp at all — shard-local stage-1 only
ever emits docs the shard genuinely hit, so an over-quota shard simply
yields fewer candidates (``doc_mask`` False), never a wrong id.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.kernels.quant import (CORPUS_FORMATS, corpus_asarray, corpus_take,
                                 quantize)
from repro.retrieval.sharded import ShardedCorpus, shard_corpus


def gather_tokens(embs: jax.Array, mask: jax.Array,
                  doc_ids: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Gather candidate token embeddings by doc id (the one shared gather).

    embs (C, L, M), mask (C, L), doc_ids (..., N) with -1 padding ->
    (..., N, L, M) embeddings + (..., N, L) mask, all-False for -1 ids.
    A quantized corpus (``QuantTokens``) gathers leaf-wise — the moved
    bytes stay compressed — and comes back as ``QuantTokens`` with the
    same (..., N, L, M) payload layout.
    """
    safe = jnp.maximum(doc_ids, 0)
    docs = corpus_take(embs, safe, axis=0)
    dmask = jnp.take(mask, safe, axis=0) & (doc_ids >= 0)[..., None]
    return docs, dmask


# ---------------------------------------------------------------------------
# Centroid router
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CentroidRouter:
    """IVF-style router state: unit centroids over doc-pooled embeddings
    plus the (centroid, shard) doc-mass table. Both arrays are replicated
    on the mesh (they are tiny next to the token index) so every shard can
    compute the identical (B, n_shards) quota table inside the shard_map
    and read its own column — routing costs zero cross-shard traffic."""

    centroids: jax.Array     # (Kc, M) f32 unit rows
    shard_mass: jax.Array    # (Kc, n_shards) f32 — docs per (centroid, shard)
    valid_docs: np.ndarray   # (n_shards,) i32 — genuine docs per shard

    @property
    def n_centroids(self) -> int:
        return self.centroids.shape[0]

    @property
    def n_shards(self) -> int:
        return self.shard_mass.shape[1]

    def route(self, queries, n_total: int, *,
              n_local: Optional[int] = None,
              healthy: Optional[np.ndarray] = None) -> np.ndarray:
        """Host-side routing API: (B, T, M) queries -> (B, n_shards) integer
        quotas summing exactly to ``n_total`` per query. Raises ``ValueError``
        (never clamps) when a quota exceeds a shard's ``valid_docs`` or the
        compiled per-shard capacity ``n_local``. ``healthy`` (n_shards,)
        bool re-routes a failed shard's quota mass onto healthy shards
        (see :func:`route_quotas`)."""
        mass = route_mass(jnp.asarray(queries, jnp.float32), self.centroids,
                          self.shard_mass)
        h = None if healthy is None else jnp.asarray(healthy, jnp.bool_)
        quotas = np.asarray(route_quotas(mass, n_total, healthy=h))
        validate_quotas(quotas, self.valid_docs, n_local=n_local)
        return quotas


def validate_quotas(quotas: np.ndarray, valid_docs: np.ndarray, *,
                    n_local: Optional[int] = None) -> None:
    """Loud-failure quota check: a routed quota larger than a shard's
    genuine doc count (or the compiled slot capacity) is a configuration
    error — raise instead of silently clamping and serving a short list."""
    quotas = np.asarray(quotas)
    valid_docs = np.asarray(valid_docs)
    peak = quotas.max(axis=0) if quotas.ndim == 2 else quotas
    for s, (q, v) in enumerate(zip(peak, valid_docs)):
        if q > v:
            raise ValueError(
                f"routed quota {int(q)} for shard {s} exceeds its "
                f"valid_docs={int(v)}; lower n_total or rebalance the "
                "corpus (quotas are never silently clamped)")
    if n_local is not None and peak.size and int(peak.max()) > n_local:
        s = int(np.argmax(peak))
        raise ValueError(
            f"routed quota {int(peak.max())} for shard {s} exceeds the "
            f"compiled per-shard capacity n_local={int(n_local)}; raise "
            "n_local or lower n_total")


@jax.jit
def _pooled_units(embs, mask):
    """Unit-normalized mean of each doc's valid token rows (C, M) f32, and
    whether the doc has any valid token (C,). Runs where the corpus lives,
    so only the (C, M) pooled rows ever reach the host."""
    mask = mask.astype(bool)
    denom = jnp.maximum(mask.sum(1, keepdims=True), 1).astype(jnp.float32)
    pooled = jnp.einsum("clm,cl->cm", embs, mask.astype(embs.dtype),
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32) / denom
    pooled /= jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True),
                          1e-9)
    return pooled, mask.any(1)


def build_router(embs, mask, *, n_shards: int, docs_per_shard: int,
                 n_centroids: int = 8, n_iters: int = 10, seed: int = 0,
                 valid_docs: Optional[np.ndarray] = None) -> CentroidRouter:
    """Build the centroid router at corpus-shard time (doc pooling where
    the corpus lives, k-means in host numpy; this is index construction,
    not the query hot path).

    Spherical k-means (Lloyd, ``n_iters`` fixed iterations, deterministic
    under ``seed``) over the doc-pooled unit embeddings of every doc with
    at least one valid token; ``shard_mass[c, s]`` counts the docs of
    cluster ``c`` resident on shard ``s`` (shard of doc = row //
    docs_per_shard — the contiguous-block placement ``shard_corpus``
    uses). Empty clusters keep their centroid and zero mass. Docs with no
    valid token carry no mass (they can never be stage-1 candidates)."""
    C, _, M = embs.shape
    if valid_docs is None:
        valid_docs = np.clip(C - docs_per_shard * np.arange(n_shards),
                             0, docs_per_shard).astype(np.int32)
    pooled, has_tok = (np.asarray(x) for x in _pooled_units(embs, mask))
    ids = np.nonzero(has_tok)[0]
    k = int(max(min(n_centroids, len(ids)), 1))
    if len(ids) == 0:
        cents = np.zeros((k, M), np.float32)
        assign = np.zeros((0,), np.int64)
    else:
        rng = np.random.default_rng(seed)
        cents = pooled[ids[rng.choice(len(ids), size=k, replace=False)]].copy()
        pts = pooled[ids]
        for _ in range(max(n_iters, 1)):
            assign = np.argmax(pts @ cents.T, axis=1)
            for c in range(k):
                sel = pts[assign == c]
                if len(sel):
                    v = sel.mean(0)
                    nrm = np.linalg.norm(v)
                    if nrm > 1e-9:
                        cents[c] = v / nrm
        assign = np.argmax(pts @ cents.T, axis=1)
    shard_mass = np.zeros((k, n_shards), np.float32)
    if len(ids):
        np.add.at(shard_mass, (assign, ids // docs_per_shard), 1.0)
    return CentroidRouter(centroids=jnp.asarray(cents),
                          shard_mass=jnp.asarray(shard_mass),
                          valid_docs=np.asarray(valid_docs, np.int32))


def route_mass(queries: jax.Array, centroids: jax.Array,
               shard_mass: jax.Array, *, n_probe: int = 0) -> jax.Array:
    """Routed per-shard candidate mass (jit/shard_map-safe).

    queries (B, T, M), centroids (Kc, M), shard_mass (Kc, S) -> (B, S):
    per-token centroid affinity relu(<q_t, c_k>) summed over tokens
    (zero-padded query tokens contribute exactly 0), optionally truncated
    to the top ``n_probe`` centroids per query, then pushed through the
    mass table. A zero-centroid router yields all-zero mass, which
    :func:`route_quotas` resolves to uniform quotas."""
    B = queries.shape[0]
    S = shard_mass.shape[1]
    if centroids.shape[0] == 0:
        return jnp.zeros((B, S), jnp.float32)
    aff = jnp.einsum("btm,km->btk", queries.astype(jnp.float32),
                     centroids.astype(jnp.float32))
    aff = jnp.sum(jax.nn.relu(aff), axis=1)                       # (B, Kc)
    if n_probe and n_probe < centroids.shape[0]:
        kth = jax.lax.top_k(aff, n_probe)[0][:, -1:]
        aff = jnp.where(aff >= kth, aff, 0.0)
    return aff @ shard_mass.astype(jnp.float32)                   # (B, S)


def route_quotas(mass: jax.Array, n_total: int,
                 healthy: Optional[jax.Array] = None) -> jax.Array:
    """Integer per-shard quotas from routed mass (jit/shard_map-safe).

    mass (B, S) >= 0 -> quotas (B, S) i32 with ``sum(quotas[b]) ==
    n_total`` EXACTLY for every query: largest-remainder rounding of the
    proportional ideal, deterministic tie-break (larger fractional part
    wins, lower shard index on exact ties). All-zero mass rows (router
    missed every centroid, or no router) fall back to uniform shares.

    ``healthy`` is an optional (S,) bool mask: unhealthy shards have
    their mass zeroed BEFORE normalisation, so their quota share is
    re-routed proportionally onto the surviving shards (failover). When
    no healthy shard has mass the fallback is uniform over the healthy
    set. ``healthy=None`` is bit-identical to the pre-failover path.
    With every shard unhealthy the quotas degenerate to the unmasked
    uniform fallback — callers are expected to fail the request before
    that point."""
    mass = jnp.maximum(mass.astype(jnp.float32), 0.0)
    B, S = mass.shape
    if healthy is None:
        tot = jnp.sum(mass, axis=-1, keepdims=True)
        frac = jnp.where(tot > 0, mass / jnp.maximum(tot, 1e-30),
                         jnp.float32(1.0 / S))
    else:
        h = jnp.asarray(healthy, jnp.bool_).reshape(S).astype(jnp.float32)
        h = jnp.where(jnp.sum(h) > 0, h, jnp.ones((S,), jnp.float32))
        mass = mass * h[None, :]
        tot = jnp.sum(mass, axis=-1, keepdims=True)
        nh = jnp.sum(h)
        # All-healthy keeps the legacy 1/S constant (bit-identical to the
        # healthy=None trace — x * 1.0 is an IEEE identity upstream too).
        fallback = jnp.where(nh >= S, jnp.full((S,), jnp.float32(1.0 / S)),
                             h / jnp.maximum(nh, 1.0))
        frac = jnp.where(tot > 0, mass / jnp.maximum(tot, 1e-30),
                         fallback[None, :])
    ideal = frac * jnp.float32(n_total)
    base = jnp.floor(ideal).astype(jnp.int32)
    rem = jnp.clip(n_total - jnp.sum(base, axis=-1), 0, S)        # (B,)
    # Priority order for the leftover units: fractional part, lower index
    # breaking exact ties (the epsilon is far below any meaningful
    # fractional difference at serving scales).
    prio = (ideal - jnp.floor(ideal)) - jnp.arange(S) * jnp.float32(1e-6)
    order = jnp.argsort(-prio, axis=-1)                           # (B, S)
    bonus = (jnp.arange(S)[None, :] < rem[:, None]).astype(jnp.int32)
    out = jnp.zeros((B, S), jnp.int32)
    return out.at[jnp.arange(B)[:, None], order].add(bonus) + base


# ---------------------------------------------------------------------------
# Corpus facade
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Corpus:
    """One attribute surface for both corpus placements.

    ``mesh=None`` is the single-device view (one shard owning everything);
    otherwise the arrays are the mesh-resident ``ShardedCorpus`` placement
    (doc dim over every axis, ragged tail padded + tracked) and ``router``
    holds the replicated centroid-router state for shard-local stage-1."""

    embs: jax.Array                      # (C_pad, L, M) f32 | bf16 |
                                         #   QuantTokens (compressed)
    mask: jax.Array                      # (C_pad, L) bool
    mesh: Optional[Mesh]
    n_docs: int
    n_shards: int
    docs_per_shard: int
    valid_docs: np.ndarray               # (n_shards,) i32
    router: Optional[CentroidRouter] = None
    pooled: Optional[jax.Array] = None
    fmt: str = "bf16"                    # resident format (CORPUS_FORMATS)

    @property
    def padded_docs(self) -> int:
        return self.n_shards * self.docs_per_shard

    def valid_docs_device(self) -> jax.Array:
        return jnp.asarray(self.valid_docs, jnp.int32)

    def gather_docs(self, doc_ids: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Candidate sub-index by global doc id (shared gather)."""
        return gather_tokens(self.embs, self.mask, doc_ids)

    def router_arrays(self) -> Tuple[jax.Array, jax.Array]:
        """(centroids, shard_mass) for the routed serving step — zero-row
        placeholders when no router was built (route_mass then yields zero
        mass and quotas fall back to uniform)."""
        if self.router is not None:
            return self.router.centroids, self.router.shard_mass
        return (jnp.zeros((0, self.embs.shape[2]), jnp.float32),
                jnp.zeros((0, self.n_shards), jnp.float32))


def build_corpus(embs, mask, *, mesh: Optional[Mesh] = None,
                 n_centroids: int = 0, router_iters: int = 10,
                 router_seed: int = 0, pooled=None,
                 corpus_format: str = "bf16") -> Corpus:
    """Build the unified corpus facade.

    With a mesh, this is ``shard_corpus`` plus (``n_centroids > 0``) the
    centroid router, built at shard time over the same contiguous-block
    placement. Without one, the single-device view: one shard owning all
    ``C`` docs (bf16 corpora stay bf16, as in ``shard_corpus``).

    ``corpus_format`` ('bf16' | 'int8' | 'residual') selects the resident
    encoding — see ``shard_corpus``. 'residual' needs centroids, so it
    bumps ``n_centroids`` to 8 when none were requested; the router built
    for stage-1 routing doubles as the codebook."""
    if corpus_format not in CORPUS_FORMATS:
        raise ValueError(f"unknown corpus format {corpus_format!r}; "
                         f"expected one of {CORPUS_FORMATS}")
    if mesh is not None:
        sc: ShardedCorpus = shard_corpus(
            embs, mask, mesh, pooled=pooled, n_centroids=n_centroids,
            router_iters=router_iters, router_seed=router_seed,
            corpus_format=corpus_format)
        return Corpus(embs=sc.embs, mask=sc.mask, mesh=mesh,
                      n_docs=sc.n_docs, n_shards=sc.n_shards,
                      docs_per_shard=sc.docs_per_shard,
                      valid_docs=sc.valid_docs, router=sc.router,
                      pooled=sc.pooled, fmt=sc.fmt)
    if embs.ndim != 3 or mask.ndim != 2 or embs.shape[:2] != mask.shape:
        raise ValueError("corpus must be (C, L, M) embs + (C, L) mask")
    C = embs.shape[0]
    if corpus_format == "residual" and not n_centroids:
        n_centroids = 8  # the residual codebook IS the router's centroids
    router = None
    if n_centroids:
        router = build_router(embs, mask, n_shards=1, docs_per_shard=C,
                              n_centroids=n_centroids, n_iters=router_iters,
                              seed=router_seed)
    if corpus_format == "bf16":
        # A device-resident corpus stays where it is: no host round trip
        # and no second copy.
        dev = jnp.asarray(embs)
        if dev.dtype != jnp.bfloat16:
            dev = dev.astype(jnp.float32)
    else:
        codebook = (None if corpus_format != "residual"
                    else np.asarray(router.centroids, np.float32))
        dev = corpus_asarray(quantize(np.asarray(embs, np.float32),
                                      corpus_format, codebook=codebook))
    return Corpus(embs=dev, mask=jnp.asarray(mask, jnp.bool_), mesh=None,
                  n_docs=C, n_shards=1, docs_per_shard=C,
                  valid_docs=np.asarray([C], np.int32), router=router,
                  pooled=None if pooled is None
                  else jnp.asarray(pooled, jnp.float32),
                  fmt=corpus_format)
