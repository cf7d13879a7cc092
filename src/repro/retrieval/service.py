"""Cluster-scale late-interaction serving (the paper's workload, distributed).

Two step flavors, both lowered by the multi-pod dry-run:

rerank_dense_step (corpus-resident scoring)
    The corpus token index (C, L, M) is sharded over ('model' [, 'pod']);
    queries are sharded over the FSDP group and replicated across corpus
    shards. The ANN stage routes each candidate to the shard that owns it
    (host-side routing table, standard in distributed retrieval): input
    ``cand_local`` (B, n_corpus_shards, N_loc) holds local doc slots. Each
    shard gathers its resident candidates, runs the dense MaxSim scorer, and
    the global top-K emerges from an all-gather of (scores, ids) — the only
    cross-shard traffic is K-sized scorecards, never token embeddings.

rerank_bandit_step (query-resident adaptive scoring)
    Queries are sharded over EVERY axis; each device gathers its queries'
    candidate embeddings once (collective gather from the sharded corpus)
    and then runs the block-synchronous Col-Bandit locally through the
    pooled cross-query reveal engine (``repro.core.frontier``): one global
    round loop for the device's whole query shard, every round's frontier
    lowered through a single ``gather_maxsim`` kernel launch, converged
    queries retired instead of riding lockstep to the slowest query.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.batched import BatchedConfig, run_batched_bandit
from repro.core.frontier import (FrontierState, init_frontier_state,
                                 run_pooled_bandit)
from repro.kernels.ops import (fused_reveal_op, gather_maxsim_op,
                               maxsim_batch_op)
from repro.kernels.quant import QuantTokens, corpus_reshape
from repro.launch.mesh import auto_axes
from repro.retrieval.ann import (STAGE1_CHUNK_DOCS, candidates_from_hits,
                                 generate_candidates_batch, token_topk)
from repro.retrieval.corpus import gather_tokens, route_mass, route_quotas
from repro.retrieval.sharded import corpus_embs_spec

_NEG = jnp.float32(-3e38)


def _local_maxsim_scores(doc_embs, doc_mask, queries):
    """(B, N, L, M) x (B, T, M) -> scores (B, N) = sum_t max_l sims.

    Lowered through the tiled ``maxsim_batch_op`` kernel path (Pallas on
    TPU, interpret on CPU, L-chunked jnp under REPRO_KERNEL_IMPL=ref) —
    no dispatch target materializes the (B, N, L, T) similarity tensor.
    ``doc_embs`` may be a quantized gather (``QuantTokens`` with a
    (B, N, L, M) payload): the kernels dequantize per VMEM block."""
    h = maxsim_batch_op(doc_embs, doc_mask, queries)          # (B, N, T)
    h = jnp.where(jnp.any(doc_mask, axis=2)[:, :, None], h, 0.0)
    return jnp.sum(h, axis=-1)


# ---------------------------------------------------------------------------
# Shared candidate-routing / gather / merge path.
#
# Every rerank flavor does the same three things around its scorer:
#   1. gather candidate token embeddings by (possibly -1-padded) doc id,
#   2. translate shard-local slots to global doc ids (shard_map flavors),
#   3. merge per-shard scorecards into a global top-K.
# These helpers are that one path; the step builders below only differ in
# the scorer they plug into the middle.
# ---------------------------------------------------------------------------

def gather_candidates(corpus_embs, corpus_mask, cand_ids):
    """Gather candidate token embeddings by global doc id.

    corpus_embs (C, L, M), corpus_mask (C, L), cand_ids (B, N) with -1
    padding -> docs (B, N, L, M), dmask (B, N, L) (all-False for padding).
    Thin alias of the facade's :func:`repro.retrieval.corpus.gather_tokens`
    (one shared gather => every flavor agrees on pad semantics).
    """
    return gather_tokens(corpus_embs, corpus_mask, cand_ids)


def _gathered_docs_spec(every, corpus_format: str):
    """shard_map PartitionSpec for a pre-gathered (B, N, L, M) candidate
    operand, batch-sharded over ``every``. Quantized formats need a
    ``QuantTokens`` OF specs mirroring the operand's pytree structure."""
    dense = P(every, None, None, None)
    if corpus_format == "bf16":
        return dense
    side = P(every, None, None)
    residual = corpus_format == "residual"
    return QuantTokens(data=dense, scales=side,
                       codes=side if residual else None,
                       codebook=P(None, None) if residual else None)


def _require_dense(corpus_embs, where: str):
    """Loud failure for the flavors whose math needs raw embedding rows
    (stage-1 kNN, pooled summaries, the legacy per-query einsum)."""
    if isinstance(corpus_embs, QuantTokens):
        raise ValueError(
            f"{where} requires a dense (bf16/f32) corpus; got a "
            f"{corpus_embs.fmt!r}-quantized one. Rebuild the corpus with "
            "corpus_format='bf16' or pick a quantization-aware flavor "
            "(dense/bandit/streaming).")


def _shard_index(every):
    """Linearized position of this shard in the (row-major) mesh axis group
    — the doc-dim shard number ``jax.sharding`` assigns this device."""
    shard_ix = jnp.int32(0)
    mul = 1
    for ax in reversed(every):
        shard_ix = shard_ix + mul * jax.lax.axis_index(ax)
        mul = mul * jax.lax.axis_size(ax)
    return shard_ix


def _shard_global_ids(cand, c_loc, every, valid_docs=None):
    """Shard-local candidate slot -> global doc id (inside shard_map).

    ``valid_docs`` is the (n_shards,) replicated ragged-tail table from
    :class:`repro.retrieval.sharded.ShardedCorpus`: shard ``s`` genuinely
    owns only ``valid_docs[s]`` of its ``c_loc`` padded rows, so a slot
    pointing past that count maps to -1 instead of a padded-tail global id
    (which, unclamped, would be a perfectly in-range id that scores the
    zero embedding — or, with an unpadded ``c_loc``, alias a real doc on
    another shard). ``None`` keeps the legacy every-shard-full contract.
    """
    shard_ix = _shard_index(every)
    owned = jnp.int32(c_loc) if valid_docs is None else valid_docs[shard_ix]
    ok = (cand >= 0) & (cand < owned)
    return jnp.where(ok, cand + shard_ix * c_loc, -1)


def _exact_winners(c_embs, c_mask, q, cand, gids, bg, n_rev, won):
    """Exact MaxSim of one shard's bandit winners, before the merge.

    A shard's bandit score for a partly revealed doc is an estimate, and
    estimates from different shards are not comparable: merging on them
    picked the wrong global top-K (sharded top-5 overlap 0.77 against 0.94
    on one device, same requests). Scoring the K winners exactly makes the
    merge compare exact scores; the winners' cells the bandit had not
    revealed yet are added to the reveal count.

    cand (B, N) local doc rows, gids (B, N) their global ids (-1 invalid),
    bg (B, K) the winners' global ids, n_rev (B,) the bandit's reveals,
    won (B, K) the winners' revealed cells -> (scores (B, K), n_rev (B,))."""
    pos = jnp.argmax(gids[:, None, :] == bg[:, :, None], axis=-1)
    rows = jnp.where(bg >= 0, jnp.take_along_axis(cand, pos, axis=1), -1)
    docs, dmask = gather_candidates(c_embs, c_mask, rows)
    s = _local_maxsim_scores(docs, dmask, q)
    s = jnp.where((bg >= 0) & jnp.isfinite(s), s, _NEG)
    fresh = jnp.where(bg >= 0, q.shape[1] - won, 0)
    return s, n_rev + jnp.sum(fresh, axis=1).astype(jnp.float32)


def _merge_scorecards(scores, gids, every, topk):
    """All-gather per-shard scorecards and take the global top-K.
    The only cross-shard traffic in the corpus-resident flavors.

    Each shard first reduces its (B, N_loc) scorecard to its local top-K —
    a slot that does not make a shard's own top-K cannot make the global
    one — so the gather moves exactly (B, K) scores + ids per shard
    whatever the candidate width. That makes the serving engine's audited
    collective budget (``analysis.hlo_audit.scorecard_budget_bytes``) a
    structural property of this merge, not an optimizer accident.

    Pad entries (gid < 0: -1-padded slots, ragged-tail clamps, short
    per-shard top-K lists) are masked to the -inf sentinel HERE, not left
    to each scorer: a shard with fewer than ``topk`` valid candidates used
    to ship its pads' raw scores into the gather, where a 0.0 pad could
    outrank a genuinely negative real score. Result sets with fewer than
    ``topk`` valid candidates overall return -1 ids for the shortfall."""
    scores = jnp.where(gids >= 0, scores, _NEG)
    if scores.shape[1] > topk:
        scores, pos = jax.lax.top_k(scores, topk)
        gids = jnp.take_along_axis(gids, pos, axis=1)
    all_scores = jax.lax.all_gather(scores, every, axis=1, tiled=True)
    all_gids = jax.lax.all_gather(gids, every, axis=1, tiled=True)
    all_scores = jnp.where(all_gids >= 0, all_scores, _NEG)
    best, pos = jax.lax.top_k(all_scores, topk)
    ids = jnp.take_along_axis(all_gids, pos, axis=1)
    return best, jnp.where(best > _NEG / 2, ids, -1)


def _chunked_over_queries(score_chunk, args, chunk=512):
    """Map ``score_chunk`` over the query batch in bounded-size chunks so the
    gathered-docs working set stays small; falls back to one call when the
    batch does not divide evenly.

    ``score_chunk`` MUST return exactly one 2-D (chunk_size, n_scores)
    array per chunk: the chunked path re-assembles with a flat
    ``reshape(B, -1)``, which would silently flatten any extra trailing
    axes (e.g. a frontier-backed scorer returning per-round diagnostics)
    into the score axis. Checked at trace time so new scorers fail loudly
    instead of corrupting the scorecard merge."""
    B = args[0].shape[0]
    chunk = min(B, chunk)
    if B % chunk == 0 and B > chunk:
        nch = B // chunk
        out = jax.lax.map(
            score_chunk,
            tuple(x.reshape(nch, chunk, *x.shape[1:]) for x in args))
        if out.ndim != 3:
            raise ValueError(
                "_chunked_over_queries: score_chunk must return a single "
                f"2-D (chunk, n_scores) array per chunk; got mapped shape "
                f"{out.shape}. Return diagnostics through a separate "
                "un-chunked path instead.")
        return out.reshape(B, -1)
    out = score_chunk(args)
    if out.ndim != 2:
        raise ValueError(
            "_chunked_over_queries: score_chunk must return a 2-D "
            f"(batch, n_scores) array; got shape {out.shape}.")
    return out


def make_rerank_dense_step(mesh: Mesh, *, topk: int = 10,
                           valid_docs=None, corpus_format: str = "bf16"):
    """Returns a jit-able step:
    (corpus_embs (C,L,M), corpus_mask (C,L), queries (B,T,M),
     cand_local (B, n_shards, N_loc) local slot ids, -1 pad)
     -> (topk_scores (B, K), topk_ids (B, K) global doc ids).

    Corpus docs shard over EVERY mesh axis (the index is the big object);
    queries are replicated (33 MB at B=4096 — cheap) so each corpus shard
    scores its resident candidates for all queries; the only cross-shard
    traffic is the (B, n_shards*N_loc) scorecard all-gather.

    ``valid_docs`` is ShardedCorpus's (n_shards,) ragged-tail table (see
    ``_shard_global_ids``); omit it for an exactly-divisible corpus.
    ``corpus_format`` must match the resident corpus (``ShardedCorpus
    .fmt``) — shard_map in_specs are built before the operands arrive, so
    the quantized pytree structure has to be declared up front."""
    mesh = auto_axes(mesh)
    every = tuple(mesh.axis_names)
    vd = None if valid_docs is None else jnp.asarray(valid_docs, jnp.int32)
    embs_spec = corpus_embs_spec(mesh, corpus_format)

    def step(corpus_embs, corpus_mask, queries, cand_local):
        def shard_fn(c_embs, c_mask, q, cand):
            # c_embs: (C_loc, L, M); q: (B, T, M) full; cand: (B, 1, N_loc)
            cand = cand[:, 0, :]                              # (B, N_loc)
            gids = _shard_global_ids(cand, c_embs.shape[0], every, vd)

            def score_chunk(args):
                q_c, cand_c = args
                docs, dmask = gather_candidates(c_embs, c_mask, cand_c)
                return _local_maxsim_scores(docs, dmask, q_c)

            scores = _chunked_over_queries(score_chunk, (q, cand))
            scores = jnp.where(gids >= 0, scores, _NEG)
            return _merge_scorecards(scores, gids, every, topk)

        return jax.shard_map(
            shard_fn, mesh=mesh, check_vma=False,
            in_specs=(embs_spec,
                      P(every, None),
                      P(None, None, None),
                      P(None, every, None)),
            out_specs=(P(None, None), P(None, None)),
        )(corpus_embs, corpus_mask, queries, cand_local)

    return step


def _bandit_one_query(cfg: BatchedConfig):
    """Per-query Col-Bandit over pre-gathered candidate embeddings — the
    legacy lockstep engine (kept for A/B benchmarking against the pooled
    frontier; select with ``engine="vmapped"``).

    Returns a closure (docs_q (N,L,M), dmask_q (N,L), q (T,M), cand_q (N,),
    a_q/b_q (N,T), key) -> (topk_scores (K,), topk_global_ids (K,),
    coverage (), rounds (), winner_revealed (K,)). The reveal op is the gathered MaxSim einsum; under vmap
    every query pays the slowest query's round count."""

    def one_query(docs_q, dmask_q, q, cand_q, a_q, b_q, key):
        def cells(doc_idx, tok_idx):
            e = jnp.take(docs_q, doc_idx, axis=0)           # (Bd, L, M)
            m = jnp.take(dmask_q, doc_idx, axis=0)
            qq = jnp.take(q, tok_idx, axis=0)               # (Bd, G, M)
            sims = jnp.einsum("blm,bgm->blg", e.astype(jnp.float32),
                              qq.astype(jnp.float32))
            sims = jnp.where(m[:, :, None], sims, _NEG)
            return jnp.max(sims, axis=1)
        res = run_batched_bandit(cells, a_q, b_q, key, cfg,
                                 doc_mask=cand_q >= 0)
        gids = jnp.where(jnp.take(cand_q, res.topk) >= 0,
                         jnp.take(cand_q, res.topk), -1)
        won = jnp.sum(jnp.take(res.revealed, res.topk, axis=0), axis=1)
        return (jnp.take(res.s_hat, res.topk), gids, res.coverage,
                res.rounds, won)

    return one_query


def _vmapped_rerank(docs, dmask, queries, cand_ids, a, b, keys,
                    cfg: BatchedConfig, *, alpha_scale=None, round_cap=None):
    """Lockstep engine: vmap the solo bandit over the query batch.

    The legacy path has no traced fidelity knobs (``alpha_scale`` /
    ``round_cap`` are accepted for signature parity and ignored) and no
    in-loop quarantine; a final finite-score guard drops any non-finite
    top-K entry to the -inf sentinel so poisoned cells can never surface
    in a result list."""
    del alpha_scale, round_cap
    _require_dense(docs, "the vmapped lockstep engine")
    scores, gids, cov, rounds, won = jax.vmap(_bandit_one_query(cfg))(
        docs, dmask, queries, cand_ids, a, b, keys)
    bad = ~jnp.isfinite(scores)
    quar = jnp.sum(bad).astype(jnp.float32)
    scores = jnp.where(bad, _NEG, scores)
    gids = jnp.where(bad, -1, gids)
    return scores, gids, cov, _lockstep_stats(rounds, quar), won


def _lockstep_stats(rounds, quarantined):
    """(occupancy, total_rounds, lockstep_waste, quarantined) for a vmapped
    run: the while_loop executes every query to max(rounds), so waste is
    what the batch PAID for already-converged queries."""
    Bq = rounds.shape[0]
    total = jnp.sum(rounds)
    trips = jnp.max(rounds)
    paid = jnp.maximum(Bq * trips, 1)
    return jnp.stack([total.astype(jnp.float32) / paid.astype(jnp.float32),
                      total.astype(jnp.float32),
                      (paid - total).astype(jnp.float32),
                      jnp.asarray(quarantined, jnp.float32)])


def _pooled_rerank(docs, dmask, queries, cand_ids, a, b, keys,
                   cfg: BatchedConfig, *, fused=None, prereveal=None,
                   prereveal_vals=None, alpha_scale=None, round_cap=None):
    """Pooled frontier engine over pre-gathered candidates.

    Stacks the (B, N, L, M) candidates to (B*N, L, M) and the query tokens
    to (B*T, M); every bandit round then reveals ALL queries' selected
    blocks with one kernel launch on query-offset indices — the
    dense-as-the-hardware-allows reveal the paper's FLOP savings need.
    ``fused=None`` (the default) lowers the round through the fused reveal
    kernel (``fused_reveal_op``: in-kernel doc gather + MaxSim +
    sufficient-statistic accumulation) everywhere except the
    ``REPRO_KERNEL_IMPL=ref`` oracle lane, which keeps the unfused
    ``gather_maxsim_op`` -> scatter chain; ``fused=False`` forces the
    chain for A/B. ``prereveal``/``prereveal_vals`` (B, N, T) seed the
    bandit with exactly-known cells (the stage-1 ANN hit values) at zero
    reveal cost. ``alpha_scale``/``round_cap`` are the traced per-call
    fidelity knobs (graceful degradation ladder — see
    :func:`repro.core.frontier.run_pooled_bandit`); ``None`` is
    bit-identical to the pre-knob path. Returns (topk_scores (B, K),
    topk_global_ids (B, K), coverage (B,), stats (4,) = [frontier
    occupancy, total rounds, lockstep waste, quarantined docs],
    winner_revealed (B, K) = revealed cells of each top-K doc)."""
    Bq, N, L, M = docs.shape
    T = queries.shape[1]
    stacked = corpus_reshape(docs, Bq * N)     # quantized: leaf-wise reshape
    stacked_mask = dmask.reshape(Bq * N, L)
    flat_q = queries.reshape(Bq * T, M)

    def cells(flat_doc, flat_tok):
        return gather_maxsim_op(stacked, stacked_mask, flat_q,
                                flat_doc, flat_tok)

    def cells_fused(flat_doc, flat_tok, new_mask):
        return fused_reveal_op(stacked, stacked_mask, flat_q,
                               flat_doc, flat_tok, new_mask)

    res = run_pooled_bandit(cells, a, b, keys, cfg, doc_mask=cand_ids >= 0,
                            compute_cells_fused=cells_fused, fused=fused,
                            prereveal=prereveal,
                            prereveal_vals=prereveal_vals,
                            alpha_scale=alpha_scale, round_cap=round_cap)
    scores = jnp.take_along_axis(res.s_hat, res.topk, axis=1)
    picked = jnp.take_along_axis(cand_ids, res.topk, axis=1)
    gids = jnp.where(picked >= 0, picked, -1)
    stats = jnp.stack([res.occupancy,
                       res.total_rounds.astype(jnp.float32),
                       res.lockstep_waste.astype(jnp.float32),
                       jnp.sum(res.quarantined).astype(jnp.float32)])
    won = jnp.sum(jnp.take_along_axis(res.revealed, res.topk[:, :, None],
                                      axis=1), axis=2)
    return scores, gids, res.coverage, stats, won


_RERANK_ENGINES = {
    "pooled": _pooled_rerank,                       # fused round (auto)
    "pooled_fused": functools.partial(_pooled_rerank, fused=True),
    "pooled_chain": functools.partial(_pooled_rerank, fused=False),
    "vmapped": _vmapped_rerank,
}


def _rerank_engine(engine: str):
    try:
        return _RERANK_ENGINES[engine]
    except KeyError:
        raise ValueError(f"unknown reveal engine: {engine!r} "
                         f"(expected one of {sorted(_RERANK_ENGINES)})"
                         ) from None


def make_rerank_bandit_step(mesh: Mesh, *, topk: int = 10,
                            alpha_ef: float = 0.3, delta: float = 0.01,
                            block_docs: int = 16, block_tokens: int = 8,
                            max_rounds: int = 64, max_block_docs: int = 0,
                            max_block_tokens: int = 0,
                            engine: str = "pooled",
                            placement: str = "query", base_seed: int = 0,
                            corpus_format: str = "bf16"):
    """Adaptive reranking step: the Col-Bandit over a sharded machine.

    ``placement`` picks which side of the gather stays resident:

    * ``"query"`` (default) — queries shard over every axis; each device
      gathers its queries' candidate embeddings once and runs ONE pooled
      frontier loop over its whole query shard (``engine="pooled"``;
      ``engine="vmapped"`` keeps the legacy lockstep path for A/B).
      Returns ``(step, in_specs, out_specs)`` for the caller to lower.
    * ``"corpus"`` — the corpus-resident shard_map flavor: the (C, L, M)
      index shards over every axis, queries replicate, and every shard
      runs the pooled frontier engine over its OWN resident candidates;
      the per-shard K-sized scorecards are the only cross-shard traffic
      (``_merge_scorecards``). Returns the shard_map-applied step with the
      ``make_sharded_serving_step`` signature (it IS that factory's
      ``flavor="bandit"``), including the ragged-tail ``valid_docs`` clamp.
    """
    if placement == "corpus":
        return make_sharded_serving_step(
            mesh, "bandit", topk=topk, alpha_ef=alpha_ef, delta=delta,
            block_docs=block_docs, block_tokens=block_tokens,
            max_rounds=max_rounds, max_block_docs=max_block_docs,
            max_block_tokens=max_block_tokens, engine=engine,
            base_seed=base_seed, corpus_format=corpus_format)
    if placement != "query":
        raise ValueError(f"unknown placement: {placement!r} "
                         "(expected 'query' or 'corpus')")
    names = tuple(mesh.axis_names)
    every = tuple(names)

    cfg = BatchedConfig(k=topk, delta=delta, alpha_ef=alpha_ef,
                        block_docs=block_docs, block_tokens=block_tokens,
                        max_rounds=max_rounds, max_block_docs=max_block_docs,
                        max_block_tokens=max_block_tokens)
    rerank = _rerank_engine(engine)

    def step(docs, dmask, queries, cand_ids, a, b):
        """docs (B, N, L, M) pre-gathered candidate embeddings (the routing
        layer gathers them from the sharded corpus as part of stage 1);
        queries (B, T, M), cand_ids (B, N), a/b (B, N, T) support bounds —
        all sharded over every axis on B.
        Returns (topk_global_ids (B, K), coverage (B,))."""
        B = queries.shape[0]
        keys = jax.random.split(jax.random.key(0), B)
        _, gids, cov, _, _ = rerank(docs, dmask, queries, cand_ids, a, b,
                                    keys, cfg)
        return gids, cov

    in_specs = (_gathered_docs_spec(every, corpus_format),  # docs (B,N,L,M)
                P(every, None, None),          # dmask (B, N, L)
                P(every, None, None),          # queries (B, T, M)
                P(every, None),                # cand_ids (B, N)
                P(every, None, None),          # a (B, N, T)
                P(every, None, None))          # b
    out_specs = (P(every, None), P(every))

    return step, in_specs, out_specs


def _budgeted_scores(docs, dmask, queries, toks):
    """Budgeted MaxSim over the selected query tokens, lowered through the
    ``gather_maxsim_op`` kernel path (the bandit's reveal kernel — a
    FLASH-MAXSIM-style fused gather+score instead of materializing the
    (b, N, L, G') similarity tensor the einsum formulation paid for).

    docs (b, N, L, M), dmask (b, N, L), queries (b, T, M),
    toks (b, N, G') -> scores (b, N) = sum over the G' selected cells.
    """
    b, N, L, M = docs.shape
    T = queries.shape[1]
    G = toks.shape[-1]
    doc_idx = jnp.arange(b * N, dtype=jnp.int32)
    # Query-offset token ids into the stacked (b*T, M) table — the same
    # stacking contract the pooled frontier feeds this kernel. Clamp
    # BEFORE offsetting: a -1 pad would otherwise land on q*T - 1, the
    # previous query's last token (the einsum path this replaced clamped
    # via take_along_axis, so keep that contract).
    tok_flat = (jnp.clip(toks.reshape(b * N, G).astype(jnp.int32), 0, T - 1)
                + (doc_idx // N * T)[:, None])
    h = gather_maxsim_op(docs.reshape(b * N, L, M), dmask.reshape(b * N, L),
                         queries.reshape(b * T, M), doc_idx, tok_flat)
    h = h.reshape(b, N, G)                                # _NEG where no
    h = jnp.where(jnp.any(dmask, 2)[:, :, None], h, 0.0)  # valid doc token
    return jnp.sum(h, axis=-1)


def make_rerank_budgeted_step(mesh: Mesh, *, topk: int = 10,
                              tokens_per_doc: int = 10, valid_docs=None):
    """§Perf: the paper's pruning INSIDE the sharded serving step.

    Identical layout to make_rerank_dense_step, but each (query, candidate)
    pair scores only ``tokens_per_doc`` of the T query tokens — the ones the
    bounds machinery selected (Doc-TopMargin order offline, or the bandit's
    reveal set online), supplied as ``tok_idx``. The scorer gathers exactly
    the selected (candidate, token) cells through ``gather_maxsim_op``
    (Pallas on TPU) instead of contracting a gathered query einsum, so
    compiled FLOPs/bytes drop by ~G'/T — Col-Bandit's coverage savings
    made visible to the roofline."""
    mesh = auto_axes(mesh)
    every = tuple(mesh.axis_names)
    vd = None if valid_docs is None else jnp.asarray(valid_docs, jnp.int32)

    def step(corpus_embs, corpus_mask, queries, cand_local, tok_idx):
        _require_dense(corpus_embs, "the budgeted serving step")

        def shard_fn(c_embs, c_mask, q, cand, toks):
            cand = cand[:, 0, :]                              # (B, N_loc)
            toks = toks[:, 0, :, :]                           # (B, N_loc, G')
            gids = _shard_global_ids(cand, c_embs.shape[0], every, vd)

            def score_chunk(args):
                q_c, cand_c, tok_c = args
                docs, dmask = gather_candidates(c_embs, c_mask, cand_c)
                return _budgeted_scores(docs, dmask, q_c, tok_c)

            scores = _chunked_over_queries(score_chunk, (q, cand, toks))
            scores = jnp.where(gids >= 0, scores, _NEG)
            return _merge_scorecards(scores, gids, every, topk)

        return jax.shard_map(
            shard_fn, mesh=mesh, check_vma=False,
            in_specs=(P(every, None, None), P(every, None),
                      P(None, None, None), P(None, every, None),
                      P(None, every, None, None)),
            out_specs=(P(None, None), P(None, None)),
        )(corpus_embs, corpus_mask, queries, cand_local, tok_idx)

    return step


def make_rerank_two_phase_step(mesh: Mesh, *, topk: int = 10,
                               survivors: int = 2, valid_docs=None):
    """§Perf H3 iteration 2: PLAID-style two-phase scoring.

    H3 iteration 1 (token pruning) taught us the dominant memory term is
    READING candidate token embeddings (L x M per doc), which query-token
    pruning cannot cut. Phase 1 therefore screens candidates on a POOLED
    doc summary (1 x M per doc — 128x fewer bytes): approx score =
    sum_t <q_t, pooled_d>. Only the top ``survivors`` of N_loc candidates
    per (query, shard) proceed to exact MaxSim scoring — the full
    (L x M)-byte reads shrink by survivors/N_loc.

    Non-survivors keep their phase-1 score in the global merge (standard
    multi-stage retrieval semantics: monotone-ish, not exact). Phase 2
    (exact MaxSim on the survivors) lowers through ``maxsim_batch_op`` via
    ``_local_maxsim_scores``; phase 1 is a plain (b, N, M) matmul with no
    token axis to tile, so it stays jnp."""
    mesh = auto_axes(mesh)
    every = tuple(mesh.axis_names)
    vd = None if valid_docs is None else jnp.asarray(valid_docs, jnp.int32)

    def step(corpus_embs, corpus_mask, corpus_pooled, queries, cand_local):
        _require_dense(corpus_embs, "the two-phase serving step")

        def shard_fn(c_embs, c_mask, c_pool, q, cand):
            cand = cand[:, 0, :]                              # (B, N_loc)
            gids = _shard_global_ids(cand, c_embs.shape[0], every, vd)

            def score_chunk(args):
                q_c, cand_c = args                            # (b,T,M),(b,N)
                safe = jnp.maximum(cand_c, 0)
                # --- phase 1: pooled screening (M bytes per doc) ---
                pooled = jnp.take(c_pool, safe, axis=0)       # (b, N, M)
                q_sum = jnp.sum(q_c.astype(jnp.float32), axis=1)   # (b, M)
                s1 = jnp.einsum("bnm,bm->bn", pooled.astype(jnp.float32),
                                q_sum)
                s1 = jnp.where(cand_c >= 0, s1, _NEG)
                # --- phase 2: exact MaxSim for the survivors only ---
                _, surv_pos = jax.lax.top_k(s1, survivors)    # (b, k2)
                surv_ids = jnp.take_along_axis(cand_c, surv_pos, axis=1)
                docs, dmask = gather_candidates(c_embs, c_mask, surv_ids)
                s2 = _local_maxsim_scores(docs, dmask, q_c)   # (b, k2)
                s2 = jnp.where(surv_ids >= 0, s2, _NEG)
                # exact scores override the phase-1 proxies
                out = s1 * 1e-3                               # keep ordering,
                out = out.at[jnp.arange(out.shape[0])[:, None],  # under exact
                             surv_pos].set(s2)
                return out

            scores = _chunked_over_queries(score_chunk, (q, cand))
            return _merge_scorecards(scores, gids, every, topk)

        return jax.shard_map(
            shard_fn, mesh=mesh, check_vma=False,
            in_specs=(P(every, None, None), P(every, None), P(every, None),
                      P(None, None, None), P(None, every, None)),
            out_specs=(P(None, None), P(None, None)),
        )(corpus_embs, corpus_mask, corpus_pooled, queries, cand_local)

    return step


# ---------------------------------------------------------------------------
# Engine-facing serving steps (repro.serve.RetrievalEngine).
#
# Same scorers as the shard_map flavors above, but expressed as plain
# jit-able programs over a replicated (or host-local) corpus: the engine
# pads every batch into a small set of static (B, T_bucket, N_bucket)
# shapes and AOT-compiles one executable per bucket, so these must be pure
# functions of statically-shaped arrays. Both flavors share the
# ``gather_candidates`` routing path and one uniform signature:
#
#   step(corpus_embs, corpus_mask, queries, cand_ids, a, b, key,
#        [alpha_scale (), round_cap ()])
#     -> (topk_scores (B, K), topk_global_ids (B, K), reveal_frac (B,),
#         stats (4,))
#
# ``reveal_frac`` is the fraction of (candidate, token) MaxSim cells the
# flavor actually computed: 1.0 for dense, the bandit's coverage (Eq. 6)
# for the adaptive flavor. ``stats`` is the reveal-engine diagnostic
# vector [frontier_occupancy, total_rounds, lockstep_waste, quarantined]:
# for the pooled engine, occupancy is the measured live-slot fraction of
# the shared frontier; for the vmapped engine it is the lockstep duty
# cycle sum(rounds) / (B * max(rounds)); dense reports [1, 0, 0, q].
# ``quarantined`` counts docs (cells for vmapped/dense) whose MaxSim hit
# a non-finite value and were excluded from the top-K — a poisoned-corpus
# signal, 0 on clean data. ``alpha_scale`` (f32) and ``round_cap`` (i32,
# <= 0 disables) are OPTIONAL traced fidelity knobs for the degradation
# ladder; omitted, the step traces bit-identical to the pre-knob engine.
# ---------------------------------------------------------------------------

def rerank_dense_step(corpus_embs, corpus_mask, queries, cand_ids, a, b,
                      key, *, topk: int = 10, alpha_scale=None,
                      round_cap=None):
    """Exact MaxSim over the candidate list; a/b/key (and the fidelity
    knobs — dense has no fidelity to trade) accepted and ignored so dense
    and bandit executables are interchangeable to the engine. Non-finite
    scores (poisoned corpus rows) are quarantined to the -inf sentinel and
    counted in ``stats[3]``."""
    del a, b, key, alpha_scale, round_cap
    docs, dmask = gather_candidates(corpus_embs, corpus_mask, cand_ids)
    scores = _local_maxsim_scores(docs, dmask, queries)
    finite = jnp.isfinite(scores)
    quar = jnp.sum((cand_ids >= 0) & ~finite).astype(jnp.float32)
    scores = jnp.where((cand_ids >= 0) & finite, scores, _NEG)
    best, pos = jax.lax.top_k(scores, topk)
    gids = jnp.take_along_axis(cand_ids, pos, axis=1)
    gids = jnp.where(best > _NEG / 2, gids, -1)
    frac = jnp.ones((queries.shape[0],), jnp.float32)
    stats = jnp.stack([jnp.float32(1.0), jnp.float32(0.0),
                       jnp.float32(0.0), quar])
    return best, gids, frac, stats


def rerank_bandit_step(corpus_embs, corpus_mask, queries, cand_ids, a, b,
                       key, *, topk: int = 10, alpha_ef: float = 0.3,
                       delta: float = 0.01, block_docs: int = 8,
                       block_tokens: int = 8, max_rounds: int = -1,
                       max_block_docs: int = 0, max_block_tokens: int = 0,
                       engine: str = "pooled", alpha_scale=None,
                       round_cap=None):
    """Adaptive Col-Bandit rerank over the candidate list.

    ``engine="pooled"`` (default) drives the whole batch through one
    pooled frontier loop — one gather_maxsim kernel launch per round,
    converged queries retired (and, with ``max_block_docs`` >
    ``block_docs``, their reveal slots redistributed to the stragglers).
    ``engine="vmapped"`` is the legacy per-query lockstep loop (it
    ignores the traced ``alpha_scale``/``round_cap`` fidelity knobs)."""
    rerank = _rerank_engine(engine)
    cfg = BatchedConfig(k=topk, delta=delta, alpha_ef=alpha_ef,
                        block_docs=block_docs, block_tokens=block_tokens,
                        max_rounds=max_rounds, max_block_docs=max_block_docs,
                        max_block_tokens=max_block_tokens)
    docs, dmask = gather_candidates(corpus_embs, corpus_mask, cand_ids)
    keys = jax.random.split(key, queries.shape[0])
    return rerank(docs, dmask, queries, cand_ids, a, b, keys, cfg,
                  alpha_scale=alpha_scale, round_cap=round_cap)[:4]


def make_serving_step(flavor: str, *, topk: int = 10, alpha_ef: float = 0.3,
                      delta: float = 0.01, block_docs: int = 8,
                      block_tokens: int = 8, max_rounds: int = -1,
                      max_block_docs: int = 0, max_block_tokens: int = 0,
                      engine: str = "pooled"):
    """Shape-bucket-aware step factory the serving engine consumes.

    Returns an un-jitted step with the uniform engine signature; the caller
    owns compilation (``RetrievalEngine`` AOT-lowers one executable per
    (flavor, token-bucket, candidate-bucket) and keeps the cache warm).
    ``engine`` picks the bandit reveal engine (pooled frontier vs legacy
    vmapped lockstep); dense ignores it."""
    _rerank_engine(engine)
    if flavor == "dense":
        return functools.partial(rerank_dense_step, topk=topk)
    if flavor == "bandit":
        return functools.partial(
            rerank_bandit_step, topk=topk, alpha_ef=alpha_ef, delta=delta,
            block_docs=block_docs, block_tokens=block_tokens,
            max_rounds=max_rounds, max_block_docs=max_block_docs,
            max_block_tokens=max_block_tokens, engine=engine)
    raise ValueError(f"unknown serving flavor: {flavor!r}")


# ---------------------------------------------------------------------------
# Continuous-batching (slot-refill) engine-facing step.
#
# The batch steps above run each admitted batch to quiescence: every query
# in the batch rides the global while_loop until the LAST one separates,
# and a new batch cannot start until the whole previous one drains. The
# streaming step instead runs the pooled bandit a bounded number of trips
# per call and hands the packed per-slot frontier state back to the host:
#
#   step(corpus_embs, corpus_mask, queries (B, T, M), cand_ids (B, N),
#        a (B, N, T), b (B, N, T), state (FrontierState), fresh (B,) bool,
#        keys (B,) per-slot PRNG keys)
#     -> (topk_scores (B, K), topk_global_ids (B, K), reveal_frac (B,),
#         stats (4,), done (B,) bool, new_state (FrontierState))
#
# The host loop (``serve.AsyncRetrievalEngine`` continuous mode) harvests
# slots with ``done`` set — their score/gid/coverage rows are final —
# refills them from the admission queue (new query tokens + candidates in
# those rows, ``fresh`` marking them) and re-enters the SAME compiled
# executable: one static (B, T, N) shape, zero recompiles, retirement
# granularity of ``trip_limit`` reveal rounds instead of a whole batch.
# Carried slots' query/candidate/bound rows must be re-presented unchanged.
# ---------------------------------------------------------------------------

def init_stream_state(B: int, N: int, T: int) -> FrontierState:
    """All-slots-retired frontier carry for a (B, N-candidate, T-token)
    streaming step — the state a continuous-batching loop starts from."""
    return init_frontier_state(B, N, T)


def make_streaming_step(*, topk: int = 10, alpha_ef: float = 0.3,
                        delta: float = 0.01, block_docs: int = 8,
                        block_tokens: int = 8, max_rounds: int = -1,
                        max_block_docs: int = 0, max_block_tokens: int = 0,
                        trip_limit: int = 4, fused=None):
    """Slot-refill serving step factory (bandit flavor only — dense has no
    rounds to slice). ``trip_limit`` is the slice length: how many global
    reveal rounds one device dispatch advances every live slot before
    control returns to the host for harvest/refill. Small values shrink
    refill latency (a retired slot idles at most ``trip_limit`` rounds);
    large values amortize dispatch overhead. ``fused`` as in
    :func:`_pooled_rerank` (None = auto by REPRO_KERNEL_IMPL)."""
    if trip_limit < 1:
        raise ValueError("trip_limit must be >= 1")
    cfg = BatchedConfig(k=topk, delta=delta, alpha_ef=alpha_ef,
                        block_docs=block_docs, block_tokens=block_tokens,
                        max_rounds=max_rounds, max_block_docs=max_block_docs,
                        max_block_tokens=max_block_tokens)

    def step(corpus_embs, corpus_mask, queries, cand_ids, a, b, state,
             fresh, keys):
        docs, dmask = gather_candidates(corpus_embs, corpus_mask, cand_ids)
        Bq, N, L, M = docs.shape
        T = queries.shape[1]
        stacked = corpus_reshape(docs, Bq * N)
        stacked_mask = dmask.reshape(Bq * N, L)
        flat_q = queries.reshape(Bq * T, M)

        def cells(flat_doc, flat_tok):
            return gather_maxsim_op(stacked, stacked_mask, flat_q,
                                    flat_doc, flat_tok)

        def cells_fused(flat_doc, flat_tok, new_mask):
            return fused_reveal_op(stacked, stacked_mask, flat_q,
                                   flat_doc, flat_tok, new_mask)

        res, new_state = run_pooled_bandit(
            cells, a, b, keys, cfg, doc_mask=cand_ids >= 0,
            compute_cells_fused=cells_fused, fused=fused,
            carry=state, fresh=fresh, trip_limit=trip_limit,
            return_state=True)
        scores = jnp.take_along_axis(res.s_hat, res.topk, axis=1)
        picked = jnp.take_along_axis(cand_ids, res.topk, axis=1)
        gids = jnp.where(picked >= 0, picked, -1)
        stats = jnp.stack([res.occupancy,
                           res.total_rounds.astype(jnp.float32),
                           res.lockstep_waste.astype(jnp.float32),
                           jnp.sum(res.quarantined).astype(jnp.float32)])
        # Harvestable = separated/no-progress OR round-capped: a slot that
        # exhausts max_rounds without separating must still leave the
        # stream, else the host would re-enter it forever. Mirrors
        # run_pooled_bandit's default when ``cfg.max_rounds <= 0``.
        mr = cfg.max_rounds
        if mr <= 0:
            mr = (N * T) // max(cfg.block_docs * cfg.block_tokens, 1) + T + 8
        harvest = new_state.done | (new_state.rounds >= mr)
        return scores, gids, res.coverage, stats, harvest, new_state

    return step


# ---------------------------------------------------------------------------
# Mesh-sharded engine-facing serving steps.
#
# Same contract as the un-sharded engine steps above, but the corpus lives
# sharded over EVERY mesh axis (repro.retrieval.sharded.ShardedCorpus) and
# candidates arrive pre-routed to their resident shard:
#
#   step(corpus_embs (C_pad, L, M), corpus_mask (C_pad, L),
#        queries (B, T, M), cand_local (B, n_shards, N_loc),
#        a_local/b_local (B, n_shards, N_loc, T),
#        valid_docs (n_shards,), seed (),
#        [healthy (n_shards,) bool, alpha_scale (), round_cap ()])
#     -> (topk_scores (B, K), topk_global_ids (B, K), reveal_frac (B,),
#         stats (n_shards, 4))
#
# Every shard scores (dense) or pooled-frontier-reranks (bandit) its OWN
# resident candidates; the only cross-shard traffic is the per-shard
# K-sized scorecard all-gather plus two scalar psums for the reveal
# fraction. ``stats`` keeps the [frontier_occupancy, total_rounds,
# lockstep_waste, quarantined] vector but PER SHARD, so the engine can
# surface shard skew (a shard whose frontier idles is a routing-imbalance
# signal) and per-shard poisoning. ``healthy`` masks failed shards out of
# the scorecard merge (their candidates score -inf everywhere, so healthy
# shards' results pass through untouched — graceful partial coverage);
# the fidelity knobs are traced scalars as in the flat steps. All three
# trailing operands are optional and default to the no-fault trace.
# ---------------------------------------------------------------------------

def make_sharded_serving_step(mesh: Mesh, flavor: str, *, topk: int = 10,
                              alpha_ef: float = 0.3, delta: float = 0.01,
                              block_docs: int = 8, block_tokens: int = 8,
                              max_rounds: int = -1, max_block_docs: int = 0,
                              max_block_tokens: int = 0,
                              engine: str = "pooled", base_seed: int = 0,
                              corpus_format: str = "bf16"):
    """Corpus-resident shard_map serving step (dense | bandit).

    The per-batch PRNG key is ``fold_in(key(base_seed), seed)`` with the
    shard index folded on top, so every (batch, shard) pair reveals an
    independent cell trajectory while the whole step stays a deterministic
    function of (base_seed, seed, inputs). ``corpus_format`` must match
    the resident ``ShardedCorpus.fmt``: a quantized corpus arrives as a
    ``QuantTokens`` pytree, and the shard_map in_specs (declared here,
    before tracing) must mirror its structure leaf-for-leaf."""
    mesh = auto_axes(mesh)
    every = tuple(mesh.axis_names)
    n_shards = 1
    for ax in every:
        n_shards *= int(mesh.shape[ax])
    if flavor not in ("dense", "bandit"):
        raise ValueError(f"unknown sharded serving flavor: {flavor!r}")
    rerank = _rerank_engine(engine)
    embs_spec = corpus_embs_spec(mesh, corpus_format)

    def step(corpus_embs, corpus_mask, queries, cand_local, a_local,
             b_local, valid_docs, seed, healthy=None, alpha_scale=None,
             round_cap=None):
        B, S, NL = cand_local.shape
        T = queries.shape[1]
        k_shard = min(topk, NL)
        if S != n_shards:
            raise ValueError(f"cand_local routed for {S} shards on a "
                             f"{n_shards}-shard mesh")
        if n_shards * k_shard < topk:
            raise ValueError(
                f"cannot assemble a global top-{topk} from {n_shards} "
                f"shards x {k_shard} candidate slots; raise N_loc")

        cfg = BatchedConfig(k=k_shard, delta=delta, alpha_ef=alpha_ef,
                            block_docs=block_docs, block_tokens=block_tokens,
                            max_rounds=max_rounds,
                            max_block_docs=max_block_docs,
                            max_block_tokens=max_block_tokens)

        # Materialize the optional fault/fidelity operands so the shard_map
        # signature stays static: defaults trace to the no-fault program.
        healthy = (jnp.ones((n_shards,), jnp.bool_) if healthy is None
                   else jnp.asarray(healthy, jnp.bool_))
        knobs = alpha_scale is not None or round_cap is not None
        asc = (jnp.float32(1.0) if alpha_scale is None
               else jnp.asarray(alpha_scale, jnp.float32))
        rcp = (jnp.int32(0) if round_cap is None
               else jnp.asarray(round_cap, jnp.int32))

        def shard_fn(c_embs, c_mask, q, cand, a_l, b_l, vd, sd, hl, a_s,
                     r_c):
            cand = cand[:, 0, :]                            # (B, N_loc)
            a_l, b_l = a_l[:, 0], b_l[:, 0]                 # (B, N_loc, T)
            gids = _shard_global_ids(cand, c_embs.shape[0], every, vd)
            # A failed shard contributes nothing: its candidates become
            # pads, so the scorecard merge masks them to -inf and the
            # psum'd reveal fraction reflects only the healthy corpus.
            valid = (gids >= 0) & hl[_shard_index(every)]
            gids = jnp.where(valid, gids, -1)
            docs, dmask = gather_candidates(c_embs, c_mask, cand)
            dmask = dmask & valid[:, :, None]
            n_cells = (jnp.sum(valid, axis=1) * T).astype(jnp.float32)

            if flavor == "dense":
                s = _local_maxsim_scores(docs, dmask, q)
                finite = jnp.isfinite(s)
                quar = jnp.sum(valid & ~finite).astype(jnp.float32)
                s = jnp.where(valid & finite, s, _NEG)
                best, pos = jax.lax.top_k(s, k_shard)
                bg = jnp.take_along_axis(gids, pos, axis=1)
                n_rev = n_cells
                stats_loc = jnp.stack([jnp.float32(1.0), jnp.float32(0.0),
                                       jnp.float32(0.0), quar])
            else:
                key = jax.random.fold_in(jax.random.key(base_seed), sd)
                key = jax.random.fold_in(key, _shard_index(every))
                keys = jax.random.split(key, cand.shape[0])
                kw = ({"alpha_scale": a_s, "round_cap": r_c} if knobs
                      else {})
                _, bg, cov, stats_loc, won = rerank(
                    docs, dmask, q, gids, a_l, b_l, keys, cfg, **kw)
                best, n_rev = _exact_winners(c_embs, c_mask, q, cand, gids,
                                             bg, cov * n_cells, won)

            tot_rev = jax.lax.psum(n_rev, every)
            tot_cells = jax.lax.psum(n_cells, every)
            frac = tot_rev / jnp.maximum(tot_cells, 1.0)
            g_best, g_ids = _merge_scorecards(best, bg, every, topk)
            return g_best, g_ids, frac, stats_loc[None, :]

        return jax.shard_map(
            shard_fn, mesh=mesh, check_vma=False,
            in_specs=(embs_spec, P(every, None),
                      P(None, None, None), P(None, every, None),
                      P(None, every, None, None), P(None, every, None, None),
                      P(None), P(), P(None), P(), P()),
            out_specs=(P(None, None), P(None, None), P(None),
                       P(every, None)),
        )(corpus_embs, corpus_mask, queries, cand_local, a_local, b_local,
          valid_docs, seed, healthy, asc, rcp)

    return step


# ---------------------------------------------------------------------------
# One-shard_map routed pipeline: shard-local stage-1 + pooled rerank.
#
# The gather flavors above still split the pipeline across two
# architectures: stage-1 kNN and candidate routing run on the HOST
# (``ann.generate_candidates`` + ``sharded.route_batch``), then the
# shard_map step consumes the pre-routed (B, n_shards, N_loc) tables. The
# routed step below retires that round-trip: centroid routing, stage-1
# kNN over the shard's own (C_loc * L, M) tokens, Eq. 15 bounds, and the
# pooled bandit rerank ALL run inside one shard_map. Candidate ids,
# embeddings and bounds never leave their shard — the only cross-shard
# traffic is the K-sized scorecard all-gather plus two scalar psums.
#
#   step(corpus_embs (C_pad, L, M), corpus_mask (C_pad, L),
#        centroids (Kc, M), shard_mass (Kc, n_shards),   # replicated router
#        queries (B, T, M), valid_docs (n_shards,), seed (),
#        [healthy (n_shards,) bool, alpha_scale (), round_cap ()])
#     -> (topk_scores (B, K), topk_global_ids (B, K), reveal_frac (B,),
#         stats (n_shards, 6))
#
# ``stats`` extends the per-shard reveal diagnostics with two routing
# columns and the quarantine count: [occupancy, total_rounds,
# lockstep_waste, mean quota share, max quota share, quarantined] — the
# skew + poisoning signals ``metrics.summary()`` surfaces. ``healthy``
# additionally re-routes a failed shard's quota mass onto the healthy
# shards (``route_quotas(..., healthy=...)``) — shard-local failover with
# zero extra communication, since the quota table is replicated anyway.
# ---------------------------------------------------------------------------

def make_routed_serving_step(mesh: Mesh, flavor: str = "bandit", *,
                             topk: int = 10, n_local: int = 16,
                             n_total: int = 0, kprime: int = 8,
                             support: Tuple[float, float] = (0.0, 1.0),
                             prereveal_ann: bool = False,
                             alpha_ef: float = 0.3, delta: float = 0.01,
                             block_docs: int = 8, block_tokens: int = 8,
                             max_rounds: int = -1, max_block_docs: int = 0,
                             max_block_tokens: int = 0,
                             engine: str = "pooled", base_seed: int = 0,
                             corpus_format: str = "bf16"):
    """Shard-local stage-1 serving step (dense | bandit), centroid-routed.

    Dense corpora only: shard-local stage-1 runs kNN over the raw
    (C_loc * L, M) token rows, which a compressed-resident corpus does not
    expose (``corpus_format != 'bf16'`` raises). Use the gather flavors
    (``make_sharded_serving_step``) for quantized corpora.

    Every shard runs the replicated centroid router over the full query
    batch (identical (B, n_shards) quota table everywhere — routing costs
    zero communication), caps its own stage-1 kNN at its quota column when
    ``n_total > 0`` (skew-aware: a shard the router sends little mass to
    emits few candidates instead of a worst-case-uniform ``n_local``), and
    feeds its local ``CandidateSet`` — Eq. 15 a/b bounds included —
    straight into the scorer. ``prereveal_ann=True`` additionally seeds
    the bandit with the stage-1 hit cells' exact values (zero reveal
    cost). Quotas are deliberately NOT validated here: shard-local stage-1
    only ever emits docs the shard genuinely hit, so an over-quota shard
    yields fewer candidates, never a wrong id — the loud ``ValueError``
    lives on the host path (``CentroidRouter.route``).

    PRNG: ``fold_in(fold_in(key(base_seed), seed), shard_index)`` — same
    determinism contract as ``make_sharded_serving_step``."""
    mesh = auto_axes(mesh)
    every = tuple(mesh.axis_names)
    n_shards = 1
    for ax in every:
        n_shards *= int(mesh.shape[ax])
    if flavor not in ("dense", "bandit"):
        raise ValueError(f"unknown routed serving flavor: {flavor!r}")
    if corpus_format != "bf16":
        raise ValueError(
            "the routed serving step requires a dense (bf16/f32) corpus: "
            "shard-local stage-1 kNN scans raw token rows, which a "
            f"{corpus_format!r}-compressed corpus does not expose. Use "
            "make_sharded_serving_step (host-routed gather flavors) for "
            "quantized corpora.")
    rerank = _rerank_engine(engine)
    if prereveal_ann and engine == "vmapped":
        raise ValueError("prereveal_ann requires a pooled reveal engine "
                         "(the vmapped lockstep path has no prereveal)")
    k_shard = min(topk, n_local)
    if n_shards * k_shard < topk:
        raise ValueError(
            f"cannot assemble a global top-{topk} from {n_shards} shards "
            f"x {k_shard} candidate slots; raise n_local")

    cfg = BatchedConfig(k=k_shard, delta=delta, alpha_ef=alpha_ef,
                        block_docs=block_docs, block_tokens=block_tokens,
                        max_rounds=max_rounds, max_block_docs=max_block_docs,
                        max_block_tokens=max_block_tokens)
    gen = functools.partial(generate_candidates_batch, kprime=kprime,
                            max_candidates=n_local, support=support)

    def step(corpus_embs, corpus_mask, centroids, shard_mass, queries,
             valid_docs, seed, healthy=None, alpha_scale=None,
             round_cap=None):
        use_healthy = healthy is not None
        knobs = alpha_scale is not None or round_cap is not None
        healthy = (jnp.ones((n_shards,), jnp.bool_) if healthy is None
                   else jnp.asarray(healthy, jnp.bool_))
        asc = (jnp.float32(1.0) if alpha_scale is None
               else jnp.asarray(alpha_scale, jnp.float32))
        rcp = (jnp.int32(0) if round_cap is None
               else jnp.asarray(round_cap, jnp.int32))

        def shard_fn(c_embs, c_mask, cents, mass, q, vd, sd, hl, a_s, r_c):
            shard_ix = _shard_index(every)
            B, T = q.shape[0], q.shape[1]
            c_loc = c_embs.shape[0]

            # Name scopes for XProf's op profile: stage-1 (routing and
            # the shard's own scan), the rerank, and the merge's
            # collectives (the all-gather and two psums).
            with jax.named_scope("routed_stage1"):
                # Centroid routing (replicated state => identical table
                # on every shard; each reads its own column). A failed
                # shard's quota mass is re-routed onto healthy shards
                # HERE, so failover costs zero extra candidates
                # system-wide.
                m = route_mass(q, cents, mass)                    # (B, S)
                if n_total:
                    quota = route_quotas(m, n_total,
                                         healthy=hl if use_healthy else None)
                    my_quota = quota[:, shard_ix]                 # (B,)
                    share = quota.astype(jnp.float32) / jnp.float32(n_total)
                else:
                    my_quota = None
                    share = jnp.full((B, n_shards), 1.0 / n_shards,
                                     jnp.float32)
                my_share = share[:, shard_ix]                     # (B,)

                # Shard-local stage-1: per-query-token kNN over this
                # shard's own (C_loc * L, M) tokens. Pad rows carry
                # all-False masks so they can never become candidates.
                cand = gen(c_embs, c_mask, q, my_quota)

                gids = _shard_global_ids(cand.doc_ids, c_loc, every, vd)
                valid = (gids >= 0) & hl[shard_ix]
                gids = jnp.where(valid, gids, -1)
            with jax.named_scope("routed_rerank"):
                docs, dmask = gather_candidates(c_embs, c_mask, cand.doc_ids)
                dmask = dmask & valid[:, :, None]
                n_cells = (jnp.sum(valid, axis=1) * T).astype(jnp.float32)

                if flavor == "dense":
                    s = _local_maxsim_scores(docs, dmask, q)
                    finite = jnp.isfinite(s)
                    quar = jnp.sum(valid & ~finite).astype(jnp.float32)
                    s = jnp.where(valid & finite, s, _NEG)
                    best, pos = jax.lax.top_k(s, k_shard)
                    bg = jnp.take_along_axis(gids, pos, axis=1)
                    n_rev = n_cells
                    stats4 = jnp.stack([jnp.float32(1.0), jnp.float32(0.0),
                                        jnp.float32(0.0), quar])
                else:
                    key = jax.random.fold_in(jax.random.key(base_seed), sd)
                    key = jax.random.fold_in(key, shard_ix)
                    keys = jax.random.split(key, B)
                    a_l = jnp.where(valid[:, :, None], cand.a, 0.0)
                    b_l = jnp.where(valid[:, :, None], cand.b, 0.0)
                    kw = {}
                    n_known = jnp.zeros((B,), jnp.float32)
                    if prereveal_ann:
                        pr = cand.known_mask & valid[:, :, None]
                        kw = dict(prereveal=pr, prereveal_vals=cand.known_vals)
                        n_known = jnp.sum(pr, axis=(1, 2)).astype(jnp.float32)
                    if knobs:
                        kw.update(alpha_scale=a_s, round_cap=r_c)
                    _, bg, cov, stats4, won = rerank(
                        docs, dmask, q, gids, a_l, b_l, keys, cfg, **kw)
                    # Reveal accounting: prereveal cells were free (stage 1
                    # already computed them), so they don't count as work.
                    best, n_rev = _exact_winners(
                        c_embs, c_mask, q, cand.doc_ids, gids, bg,
                        jnp.maximum(cov * n_cells - n_known, 0.0), won)

            with jax.named_scope("routed_merge"):
                tot_rev = jax.lax.psum(n_rev, every)
                tot_cells = jax.lax.psum(n_cells, every)
                frac = tot_rev / jnp.maximum(tot_cells, 1.0)
                g_best, g_ids = _merge_scorecards(best, bg, every, topk)
            # Column order keeps quarantine LAST so the routing-skew
            # columns stay at the indices metrics consumers already read.
            stats_loc = jnp.concatenate(
                [stats4[:3], jnp.stack([jnp.mean(my_share),
                                        jnp.max(my_share)]),
                 stats4[3:]])[None, :]
            return g_best, g_ids, frac, stats_loc

        return jax.shard_map(
            shard_fn, mesh=mesh, check_vma=False,
            in_specs=(P(every, None, None), P(every, None),
                      P(None, None), P(None, None),
                      P(None, None, None), P(None), P(), P(None), P(),
                      P()),
            out_specs=(P(None, None), P(None, None), P(None),
                       P(every, None)),
        )(corpus_embs, corpus_mask, centroids, shard_mass, queries,
          valid_docs, seed, healthy, asc, rcp)

    return step


def make_sharded_stage1(mesh: Mesh, *, kprime: int = 8,
                        max_candidates: int = 64,
                        support: Tuple[float, float] = (0.0, 1.0)):
    """Host-path stage-1 over a mesh-resident corpus: the same
    ``CandidateSet`` as ``generate_candidates`` over the whole index.

    Each shard scans its own token rows for every query token's top-k'
    (``ann.token_topk``); the per-shard lists (B, T, k') are all-gathered
    and merged in shard order, which is global position order, so ties
    resolve exactly as one top-k over the whole index would. The index
    itself never leaves its shard.

      stage1(corpus_embs (C_pad, L, M), corpus_mask (C_pad, L),
             queries (B, T, M)) -> (doc_ids (B, N), a (B, N, T), b (B, N, T))
    """
    mesh = auto_axes(mesh)
    every = tuple(mesh.axis_names)

    def local_hits(c_embs, c_mask, q):
        c_loc, L, _ = c_embs.shape
        kp = min(kprime, c_loc * L)
        vals, docs = token_topk(c_embs, c_mask, q, kp, STAGE1_CHUNK_DOCS)
        docs = docs + _shard_index(every) * c_loc
        return (jax.lax.all_gather(vals, every, axis=2, tiled=True),
                jax.lax.all_gather(docs, every, axis=2, tiled=True))

    def stage1(corpus_embs, corpus_mask, queries):
        C = corpus_embs.shape[0]
        vals, docs = jax.shard_map(
            local_hits, mesh=mesh, check_vma=False,
            in_specs=(P(every, None, None), P(every, None),
                      P(None, None, None)),
            out_specs=(P(None, None, None), P(None, None, None)),
        )(corpus_embs, corpus_mask, queries)              # (B, T, S*k')
        kp = min(kprime, C * corpus_embs.shape[1])
        vals, pos = jax.lax.top_k(vals, kp)
        docs = jnp.take_along_axis(docs, pos, axis=2)

        def one(v, d):
            cs = candidates_from_hits(v, d, C, max_candidates=max_candidates,
                                      support=support)
            return cs.doc_ids, cs.a, cs.b
        with jax.named_scope("stage1_candidates"):
            return jax.vmap(one)(vals, docs)

    return stage1
