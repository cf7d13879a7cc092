"""Mesh-resident corpus for the sharded serving flavors.

The paper's drop-in claim holds only while the (C, L, M) token index fits
on one device; at production scale the index is sharded by construction
(ColBERTv2's residual-compressed shards, our {"data": 16, "model": 16} and
pod meshes). :class:`ShardedCorpus` is the one object that owns that
placement:

  * the doc dim is padded to a multiple of the mesh's shard count and
    placed with ``NamedSharding`` over EVERY mesh axis
    (``repro.dist.sharding.corpus_specs``) — shard ``s`` owns the
    contiguous global rows ``[s*docs_per_shard, (s+1)*docs_per_shard)``,
    so a real doc's padded-global id IS its original id;
  * the ragged tail is explicit metadata, not a convention: ``valid_docs``
    counts the genuine docs per shard (the trailing shards of an odd-size
    corpus own fewer, possibly zero), and the shard_map flavors clamp their
    global-id math against it (`service._shard_global_ids`);
  * :func:`route_candidates` is the host-side stage-1 routing table:
    global candidate ids -> per-shard local slot lists, the layout every
    corpus-resident ``shard_map`` flavor consumes.

Pad rows carry an all-False token mask and zero embeddings, so they can
never contribute score mass even before the id clamp drops them.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding

from repro.dist.sharding import corpus_axes, corpus_specs
from repro.kernels.quant import CORPUS_FORMATS, QuantTokens, quantize
from repro.launch.mesh import auto_axes


@dataclasses.dataclass(frozen=True)
class ShardedCorpus:
    """A (C, L, M) token index resident on a device mesh.

    ``embs``/``mask`` (and ``pooled`` when present) are device arrays whose
    doc dim is sharded over every mesh axis; ``n_docs`` is the TRUE corpus
    size, ``docs_per_shard * n_shards`` the padded one.

    ``fmt`` is the resident corpus format (``kernels.quant.CORPUS_FORMATS``).
    For ``int8``/``residual``, ``embs`` is a ``QuantTokens`` pytree whose
    payload/sidecar leaves shard exactly like a dense corpus (doc dim over
    every axis; the residual codebook replicates) — the kernels dequantize
    per VMEM block, so shard HBM holds compressed bytes only.
    """

    embs: jax.Array                      # (C_pad, L, M) f32 | bf16 |
                                         #   QuantTokens (int8 + sidecars)
    mask: jax.Array                      # (C_pad, L) bool — pads all-False
    mesh: Mesh
    n_docs: int                          # genuine docs (C)
    n_shards: int
    docs_per_shard: int                  # C_pad // n_shards
    valid_docs: np.ndarray               # (n_shards,) i32 genuine docs/shard
    pooled: Optional[jax.Array] = None   # (C_pad, M) two-phase summaries
    # Centroid-router state for shard-local stage-1 (a
    # ``repro.retrieval.corpus.CentroidRouter``; typed as object to keep
    # this module free of a corpus.py import cycle). Replicated arrays.
    router: Optional[object] = None
    fmt: str = "bf16"                    # resident format (CORPUS_FORMATS)

    @property
    def padded_docs(self) -> int:
        return self.n_shards * self.docs_per_shard

    def valid_docs_device(self) -> jax.Array:
        """(n_shards,) i32, replicated — the clamp table the shard_map
        flavors index by their own axis position."""
        return jnp.asarray(self.valid_docs, jnp.int32)


def corpus_embs_spec(mesh: Mesh, corpus_format: str = "bf16"):
    """The shard_map/``NamedSharding`` spec for a corpus ``embs`` operand.

    Dense formats get the plain ``corpus_specs(mesh)["embs"]`` PartitionSpec;
    ``int8``/``residual`` get a ``QuantTokens`` OF PartitionSpecs whose tree
    structure matches the resident pytree leaf-for-leaf (shard_map in_specs
    must mirror operand structure). Callers building specs before they hold
    the corpus pass the format string instead of inspecting arrays."""
    specs = corpus_specs(mesh)
    if corpus_format == "bf16":
        return specs["embs"]
    if corpus_format not in CORPUS_FORMATS:
        raise ValueError(f"unknown corpus format {corpus_format!r}; "
                         f"expected one of {CORPUS_FORMATS}")
    return QuantTokens(
        data=specs["embs"], scales=specs["scales"],
        codes=specs["codes"] if corpus_format == "residual" else None,
        codebook=specs["codebook"] if corpus_format == "residual" else None)


def shard_corpus(embs, mask, mesh: Mesh, *, pooled=None, router=None,
                 n_centroids: int = 0, router_iters: int = 10,
                 router_seed: int = 0,
                 corpus_format: str = "bf16") -> ShardedCorpus:
    """Pad the doc dim to the mesh's shard count and place every corpus
    array with its ``corpus_specs`` NamedSharding.

    A ``bfloat16`` corpus stays bfloat16 on the mesh (half the per-shard
    HBM; every kernel op accumulates in f32); other dtypes normalize to
    f32.

    ``corpus_format`` selects the resident encoding (``"bf16"`` keeps the
    dense behavior above — source dtype passes through). ``"int8"``
    quantizes each (doc, token) row symmetrically against a resident bf16
    scale; ``"residual"`` additionally stores a centroid id per row and
    int8-quantizes only the residual against the router codebook
    (ColBERTv2-style), so the residual path REQUIRES a router —
    ``n_centroids`` defaults to 8 when neither a router nor a count is
    given. Quantization happens host-side on the padded arrays, so pad
    rows encode with scale 0 and decode to exact zeros (int8) or
    ``centroids[0]`` (residual); either way their all-False token mask
    keeps them out of every max.

    ``n_centroids > 0`` additionally builds the shard-local stage-1
    centroid router (``repro.retrieval.corpus.build_router``) over the
    same contiguous-block placement, at shard time; a prebuilt ``router``
    may be passed instead. Either way its (tiny) arrays are placed
    replicated on the mesh."""
    if corpus_format not in CORPUS_FORMATS:
        raise ValueError(f"unknown corpus format {corpus_format!r}; "
                         f"expected one of {CORPUS_FORMATS}")
    # A device-resident corpus (a jax.Array, possibly already laid out on
    # the mesh) is padded and placed on device; a host corpus goes from
    # host memory straight to its shards.
    xp = jnp if isinstance(embs, jax.Array) else np
    embs = xp.asarray(embs)
    if embs.dtype != jnp.bfloat16:
        embs = embs.astype(np.float32)
    mask = xp.asarray(mask, bool)
    if embs.ndim != 3 or mask.ndim != 2 or embs.shape[:2] != mask.shape:
        raise ValueError("corpus must be (C, L, M) embs + (C, L) mask")
    C = embs.shape[0]
    mesh = auto_axes(mesh)
    n_shards = int(np.prod([mesh.shape[a] for a in corpus_axes(mesh)]))
    c_loc = -(-max(C, 1) // n_shards)            # ceil; >=1 so shapes stay real
    pad = n_shards * c_loc - C
    if pad:
        embs = xp.pad(embs, ((0, pad), (0, 0), (0, 0)))
        mask = xp.pad(mask, ((0, pad), (0, 0)))  # pads False => masked out
    valid = np.clip(C - c_loc * np.arange(n_shards), 0, c_loc).astype(np.int32)
    specs = corpus_specs(mesh)
    put = lambda x, s: jax.device_put(x, NamedSharding(mesh, s))
    pooled_dev = None
    if pooled is not None:
        pooled = np.asarray(pooled, np.float32)
        if pad:
            pooled = np.pad(pooled, ((0, pad), (0, 0)))
        pooled_dev = put(pooled, specs["pooled"])
    if corpus_format == "residual" and router is None and not n_centroids:
        n_centroids = 8  # the residual codebook IS the router's centroids
    if router is None and n_centroids:
        # late import: corpus.py is the facade ABOVE this module
        from repro.retrieval.corpus import build_router
        router = build_router(embs, mask, n_shards=n_shards,
                              docs_per_shard=c_loc,
                              n_centroids=n_centroids, n_iters=router_iters,
                              seed=router_seed, valid_docs=valid)
    codebook = None
    if corpus_format == "residual":
        if router is None:
            raise ValueError(
                "corpus_format='residual' needs a centroid codebook: pass "
                "a prebuilt router or n_centroids > 0")
        codebook = np.asarray(router.centroids, np.float32)
    if router is not None:
        router = dataclasses.replace(
            router,
            centroids=put(np.asarray(router.centroids, np.float32),
                          specs["centroids"]),
            shard_mass=put(np.asarray(router.shard_mass, np.float32),
                           specs["shard_mass"]))
    if corpus_format == "bf16":
        embs_dev = put(embs, specs["embs"])
    else:
        qt = quantize(np.asarray(embs, np.float32), corpus_format,
                      codebook=codebook)
        embs_dev = QuantTokens(
            data=put(np.asarray(qt.data), specs["embs"]),
            scales=put(np.asarray(qt.scales), specs["scales"]),
            codes=None if qt.codes is None else
            put(np.asarray(qt.codes), specs["codes"]),
            codebook=None if qt.codebook is None else
            put(np.asarray(qt.codebook), specs["codebook"]))
    return ShardedCorpus(
        embs=embs_dev, mask=put(mask, specs["mask"]),
        mesh=mesh, n_docs=C, n_shards=n_shards, docs_per_shard=c_loc,
        valid_docs=valid, pooled=pooled_dev, router=router,
        fmt=corpus_format)


def _routing_placement(cand_ids: np.ndarray, docs_per_shard: int,
                       n_shards: int, n_local: int):
    """The one gid -> (row, shard, slot) placement both routing functions
    share: candidate gid lands on shard ``gid // docs_per_shard``, packed
    to the front of that shard's slot list in the query's original
    candidate order. Returns (rows, cols, shards, slots) index arrays —
    ``out[rows, shards, slots] = f(cand_ids[rows, cols])`` — so ids and
    per-candidate payloads can never disagree about where a candidate
    went. Vectorized: this runs per served batch on the engine's
    latency-critical path."""
    cand_ids = np.asarray(cand_ids)
    rows, cols = np.nonzero(cand_ids >= 0)
    gids = cand_ids[rows, cols]
    if gids.size and int(gids.max()) >= n_shards * docs_per_shard:
        raise ValueError(
            f"candidate id {int(gids.max())} outside the padded corpus "
            f"({n_shards * docs_per_shard} rows)")
    shards = gids // docs_per_shard
    # Stable grouping key (row, shard): rank within the group = index minus
    # the group's first index, found by searchsorted on the sorted keys.
    key = rows.astype(np.int64) * n_shards + shards
    order = np.argsort(key, kind="stable")
    key_sorted = key[order]
    rank = np.empty_like(order)
    rank[order] = (np.arange(len(order))
                   - np.searchsorted(key_sorted, key_sorted, side="left"))
    if rank.size and int(rank.max()) >= n_local:
        i = rows[int(np.argmax(rank))]
        raise ValueError(
            f"query {int(i)} routes more than n_local={n_local} candidates "
            "to one shard; raise n_local (it may go up to N)")
    return rows, cols, shards, rank


def route_candidates(cand_ids: np.ndarray, docs_per_shard: int,
                     n_shards: int, *, n_local: Optional[int] = None,
                     ) -> np.ndarray:
    """Host-side stage-1 routing: global ids -> per-shard local slots.

    cand_ids (B, N) with -1 padding -> (B, n_shards, n_local), -1 padded:
    candidate gid goes to shard ``gid // docs_per_shard``, PACKED to the
    front of that shard's slot list in the query's original candidate
    order, carrying the local doc row ``gid % docs_per_shard`` as the
    stored value. ``n_local`` defaults to N (the worst case: every
    candidate resident on one shard), keeping the routed shape static per
    candidate bucket — the zero-recompile contract the engine needs.
    """
    cand_ids = np.asarray(cand_ids)
    B, N = cand_ids.shape
    n_local = N if n_local is None else n_local
    rows, cols, shards, slots = _routing_placement(
        cand_ids, docs_per_shard, n_shards, n_local)
    out = np.full((B, n_shards, n_local), -1, np.int32)
    out[rows, shards, slots] = cand_ids[rows, cols] % docs_per_shard
    return out


def route_batch(cand_ids: np.ndarray, payloads, docs_per_shard: int,
                n_shards: int, *, n_local: Optional[int] = None):
    """Route ids plus any number of aligned (B, N, ...) payloads with ONE
    placement computation — what the engine's latency path calls instead
    of ``route_candidates`` + ``route_aligned`` per payload. Returns
    ``(cand_local, [routed payloads...])``."""
    cand_ids = np.asarray(cand_ids)
    B, N = cand_ids.shape
    n_local = N if n_local is None else n_local
    rows, cols, shards, slots = _routing_placement(
        cand_ids, docs_per_shard, n_shards, n_local)
    cand_local = np.full((B, n_shards, n_local), -1, np.int32)
    cand_local[rows, shards, slots] = cand_ids[rows, cols] % docs_per_shard
    routed = []
    for values in payloads:
        values = np.asarray(values)
        out = np.zeros((B, n_shards, n_local) + values.shape[2:],
                       values.dtype)
        out[rows, shards, slots] = values[rows, cols]
        routed.append(out)
    return cand_local, routed


def route_aligned(values: np.ndarray, cand_ids: np.ndarray,
                  cand_local: np.ndarray, docs_per_shard: int) -> np.ndarray:
    """Carry per-candidate payloads (e.g. the (B, N, T) support bounds)
    through the same routing ``route_candidates`` applied to the ids:
    values (B, N, ...) -> (B, n_shards, n_local, ...) aligned with
    ``cand_local``, zero-filled where cand_local is -1."""
    values = np.asarray(values)
    B, n_shards, n_local = cand_local.shape
    rows, cols, shards, slots = _routing_placement(
        cand_ids, docs_per_shard, n_shards, n_local)
    out = np.zeros((B, n_shards, n_local) + values.shape[2:], values.dtype)
    out[rows, shards, slots] = values[rows, cols]
    return out
