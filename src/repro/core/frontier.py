"""Pooled cross-query reveal engine — continuous batching for Col-Bandit.

``jax.vmap(one_query)`` over a ``while_loop`` runs every query of a serving
batch in lockstep to the SLOWEST query's round count: converged queries keep
burning reveal-kernel slots until the last straggler separates. This module
replaces that with one global ``while_loop`` driving all Q queries at once:

  1. every round, each still-active query runs the shared LUCB block
     selection (``repro.core.batched._round_select`` — bit-identical policy
     and PRNG stream to the solo bandit),
  2. the selected (doc, token) blocks of ALL active queries are pooled into
     a single fixed-capacity frontier: doc ids are query-offset into the
     stacked (Q*N, L, M) candidate tensor, token ids into the stacked
     (Q*T, M) query-token table,
  3. the whole frontier lowers through ONE reveal launch per round,
  4. per-query done-masks retire finished queries: their slots drop out of
     the frontier (occupancy is measured), their round counters freeze, and
     — with ``cfg.max_block_docs > block_docs`` (and/or
     ``cfg.max_block_tokens > block_tokens``) — their freed capacity is
     reallocated to still-active queries, which then reveal bigger doc
     and/or token blocks per round and converge in fewer global loop trips.

Two ROUND BODIES lower step 3, selected by ``fused=`` (default: fused
unless ``REPRO_KERNEL_IMPL=ref``):

* **chain** (the ``ref``-lane oracle): cells come from the abstract
  ``compute_cells`` gather, and the statistics update is the classic
  ``_apply_block_reveal`` scatter chain over a stacked (Q*N, T)
  ``BanditState`` — five separate scatters per round, each an HBM
  round-trip at serving scale.
* **fused**: one reveal launch returns the cell values AND the per-row
  sufficient-statistic deltas (``kernels.ops.fused_reveal_op`` — in-kernel
  doc gather, VMEM-resident running max, in-kernel stat accumulation), and
  the whole state update collapses to ONE scatter-min into a sentinel-
  encoded (Q*N, T) cell-value table (``_UNREV`` marks unrevealed; the
  revealed mask is derived by comparison, fusing into the interval math)
  plus ONE 3-column scatter-add of the (n, total, total_sq) statistics.
  When no slot growth is configured the frontier also skips compaction —
  capacity equals the selection width, so the flat (Q*W) selections feed
  the launch directly (dead slots ride along as masked no-ops).

Both bodies make bit-identical per-query reveal decisions from identical
statistics: the fused body is a re-plumbing of WHERE values and statistics
are computed, never WHICH cells a query reveals. That invariant is what the
chain-vs-fused parity tests pin down, on top of the existing guarantee that
with ``max_block_docs == 0`` each query's trajectory is exactly the solo
``run_batched_bandit`` trajectory under the same key.
"""
from __future__ import annotations

import functools
import os
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import bounds as B
from repro.core.bandit import _select_arms, _topk_mask
from repro.core.batched import (BatchedConfig, _apply_block_reveal,
                                _round_select)
from repro.core.state import BanditState

_NEG = jnp.float32(-3e38)
# Fused-round cell table sentinel: unrevealed cells hold _UNREV; anything
# below _REV_THRESH is a revealed value. Real MaxSim values are bounded far
# below 1.5e38 (the all-masked-document sentinel is -3e38, also below).
_UNREV = jnp.float32(3e38)
_REV_THRESH = jnp.float32(1.5e38)
# Finite-score guard: a revealed cell that comes back NaN/Inf (poisoned
# corpus row, kernel bug) is recorded as _QUAR instead — finite, so the
# sufficient statistics stay well-defined (no NaN mean, no inf total_sq),
# yet far below any genuine MaxSim value, so the doc can never win the
# top-K. _QUAR_THRESH separates quarantined cells from real ones at
# finalize time (real |MaxSim| is O(|q||d|) << 1e4).
_QUAR = jnp.float32(-3e4)
_QUAR_THRESH = jnp.float32(-1e4)

# Cell contract (pooled): compute_cells(flat_doc (S,), flat_tok (S, G))
# -> (S, G), where flat_doc indexes the stacked (Q*N, ...) doc axis and
# flat_tok the stacked (Q*T, ...) query-token axis (doc q*N+i pairs only
# with tokens q*T+t of the SAME query q). This is exactly the contract
# ``kernels.ops.gather_maxsim_op`` lowers on the stacked tensors. The
# fused round extends it: compute_cells_fused(flat_doc, flat_tok,
# new_mask) -> (vals (S, G), stats (S, 3)) with stats rows
# [d_count, d_total, d_total_sq] summed over new_mask cells — the
# ``kernels.ops.fused_reveal_op`` contract.


def _auto_fused() -> bool:
    """Round-body default: the fused Pallas round everywhere except the
    ``REPRO_KERNEL_IMPL=ref`` lane, which keeps the unfused scatter chain
    as the oracle (the env var is ``kernels.ops._impl``'s dispatch knob;
    core reads it directly rather than importing the kernels layer)."""
    return os.environ.get("REPRO_KERNEL_IMPL", "auto") != "ref"


def _with_stats(compute_cells: Callable) -> Callable:
    """Adapt a plain gather-style cell source to the fused-round contract
    by deriving the statistic deltas in XLA (the reductions fuse with the
    gather; kernel-backed sources compute them in-kernel instead)."""

    def cells_fused(flat_doc, flat_tok, new_mask):
        v = compute_cells(flat_doc, flat_tok)
        nf = new_mask.astype(jnp.float32)
        vm = jnp.where(new_mask, v, 0.0)
        return v, jnp.stack([jnp.sum(nf, axis=-1), jnp.sum(vm, axis=-1),
                             jnp.sum(vm * v, axis=-1)], axis=-1)

    return cells_fused


class FrontierState(NamedTuple):
    """Resumable pooled-frontier carry — the slot-level continuous-batching
    state. The five BanditState statistics collapse to one sentinel-encoded
    cell table + one packed (n, total, total_sq) block; ``key``/``rounds``/
    ``done`` are per-SLOT. A serving loop holds one of these across
    ``run_pooled_slice`` calls: when slot q retires (``done[q]``), the host
    harvests its results and refills the slot with a new query — passing
    ``fresh[q]=True`` on the next call resets exactly that slot's rows
    (fresh init reveal included) while every other slot's statistics carry
    forward untouched. Both round bodies (fused and chain) read and write
    this same packed encoding at the call boundary, so a stream may even
    alternate bodies between slices.
    """

    cellvals: jax.Array    # (Q*N, T) f32 — _UNREV where unrevealed
    stats: jax.Array       # (Q*N, 3) f32 — [n, total, total_sq]
    key: jax.Array         # (Q,) per-query PRNG keys
    rounds: jax.Array      # (Q,) i32 — frozen at retirement
    done: jax.Array        # (Q,) bool


# Backwards-compatible internal alias (pre-resume name).
_FusedState = FrontierState


def init_frontier_state(Q: int, N: int, T: int) -> FrontierState:
    """An all-slots-empty carry: every slot retired (``done``), zero
    statistics, cell tables reading as revealed-empty (value 0.0 < the
    sentinel threshold, matching how both bodies encode invalid docs).
    Feed it as the first ``carry`` of a streaming loop — slots come alive
    only when refilled via ``fresh``."""
    return FrontierState(
        cellvals=jnp.zeros((Q * N, T), jnp.float32),
        stats=jnp.zeros((Q * N, 3), jnp.float32),
        key=jax.random.split(jax.random.key(0), Q),
        rounds=jnp.zeros((Q,), jnp.int32),
        done=jnp.ones((Q,), jnp.bool_))


class PooledResult(NamedTuple):
    topk: jax.Array            # (Q, K) i32 — per-query top-K doc slots
    s_hat: jax.Array           # (Q, N) f32 — final score estimates
    coverage: jax.Array        # (Q,) f32 — Eq. 6 per query
    reveals: jax.Array         # (Q,) i32 — |Omega_q|
    rounds: jax.Array          # (Q,) i32 — per-query LUCB rounds (frozen at
                               #   retirement; == solo rounds when blocks
                               #   are fixed)
    separated: jax.Array       # (Q,) bool — stopped via LCB >= UCB
    revealed: jax.Array        # (Q, N, T) bool — final observation sets
    trips: jax.Array           # () i32 — global while_loop iterations
                               #   (== max(rounds) by construction)
    total_rounds: jax.Array    # () i32 — sum(rounds): reveal rounds actually
                               #   attributable to queries
    lockstep_waste: jax.Array  # () i32 — Q*trips - total_rounds: rounds a
                               #   vmapped lockstep loop would have burned on
                               #   already-converged queries
    occupancy: jax.Array       # () f32 — mean fraction of frontier slots
                               #   holding live reveal work across trips
    quarantined: jax.Array     # (Q,) i32 — candidate docs whose revealed
                               #   cells included a non-finite value (the
                               #   finite-score guard excluded them from
                               #   the top-K; 0 everywhere on clean data)


def run_pooled_bandit(
    compute_cells,
    a: jax.Array,                # (Q, N, T) lower support per cell
    b: jax.Array,                # (Q, N, T) upper support per cell
    keys: jax.Array,             # (Q,) per-query PRNG keys
    cfg: BatchedConfig,
    *,
    doc_mask: Optional[jax.Array] = None,   # (Q, N) bool valid candidates
    compute_cells_fused=None,    # fused contract; derived when omitted
    fused: Optional[bool] = None,           # None => _auto_fused()
    prereveal: Optional[jax.Array] = None,      # (Q, N, T) bool — cells whose
    prereveal_vals: Optional[jax.Array] = None,  # exact values are known
    carry: Optional[FrontierState] = None,  # resume from a prior slice
    fresh: Optional[jax.Array] = None,      # (Q,) bool — slots to (re)init
    trip_limit: int = 0,                    # >0: pause after this many trips
    return_state: bool = False,             # also return the FrontierState
    alpha_scale=None,            # traced () f32 >= 1: per-call fidelity knob
    round_cap=None,              # traced () i32: per-call round cap (<=0 off)
):
    """``prereveal``/``prereveal_vals`` seed the bandit with cells whose
    exact values an earlier stage already computed (e.g. the stage-1 ANN
    hit cells, Eq. 15's exact-``h`` branch) at zero reveal cost: they enter
    the sufficient statistics before round 0, count as revealed for the
    selection policy (never re-revealed) and for ``reveals``/``coverage``.
    Both round bodies apply them identically.

    Streaming (continuous batching) extensions — all default-off, and the
    default path is trace-identical to the one-shot engine:

    * ``carry`` resumes from a prior call's :class:`FrontierState` instead
      of a cold start. ``fresh`` (default all-False when carrying, forced
      all-True otherwise) marks the slots being REFILLED this call: a fresh
      slot is fully re-initialized from this call's ``a``/``b``/``keys``/
      ``prereveal`` (init reveal included, prereveal masked to fresh slots)
      while carried slots' statistics, keys, round counters and retirement
      flags pass through untouched. Carried slots' ``a``/``b``/``doc_mask``
      must be re-presented unchanged — the packed state holds statistics,
      not supports.
    * ``trip_limit > 0`` pauses the global while_loop after that many trips
      even with queries still active, so the host can harvest retired slots
      mid-flight. Per-query results in the returned :class:`PooledResult`
      are only FINAL for slots with ``done`` set (or every slot once the
      loop ran to quiescence).
    * ``return_state=True`` returns ``(PooledResult, FrontierState)``.

    Degraded-fidelity knobs (serve-layer ladder; both TRACED scalars, so
    one compiled executable serves every fidelity level with zero
    recompiles — ``serfling_radius`` is linear in ``alpha_ef``, making the
    scale exact, not an approximation):

    * ``alpha_scale`` multiplies the effective ``alpha_ef`` for this call
      (wider radii => earlier separation => fewer reveals). ``None`` keeps
      the static config value with a trace identical to pre-knob code;
      passing ``1.0`` is numerically bit-identical to ``None``.
    * ``round_cap`` caps this call's per-query reveal rounds below the
      static ``cfg.max_rounds`` (values ``<= 0`` disable the cap).

    Finite-score guard (always on): any revealed cell that comes back
    non-finite is recorded as the ``_QUAR`` sentinel; its doc is excluded
    from the final top-K and counted in ``PooledResult.quarantined``. On
    all-finite data every guard op is an identity, so clean runs stay
    bit-identical to pre-guard code.
    """
    if fused is None:
        fused = _auto_fused()
    Q, N, T = a.shape
    if carry is None:
        fresh = jnp.ones((Q,), jnp.bool_)
    elif fresh is None:
        fresh = jnp.zeros((Q,), jnp.bool_)
    fresh = fresh.astype(jnp.bool_)
    fresh_rows = jnp.broadcast_to(fresh[:, None], (Q, N)).reshape(Q * N)
    k = cfg.k
    G = cfg.block_tokens
    half = max(cfg.block_docs // 2, 1)
    # Selection widths per query: fixed (== solo) unless growth is enabled.
    # Clamped to N / T: a query can never hold more than its N candidate
    # rows or T tokens, and an unclamped width would surface as an opaque
    # top_k shape error (reachable from EngineConfig alone on small
    # buckets).
    half_w = min(max(cfg.max_block_docs // 2, half), max(N, 1))
    W = 2 * half_w                           # per-query selection rows
    G_cap = min(max(cfg.max_block_tokens, G), max(T, 1))  # token sel width
    F = Q * 2 * half                         # frontier capacity (slots)
    max_rounds = cfg.max_rounds
    if max_rounds <= 0:
        max_rounds = (N * T) // max(cfg.block_docs * G, 1) + T + 8
    if round_cap is not None:
        # Traced per-call cap: <= 0 disables (the compiled program is one
        # executable for every ladder level). Python-int path untouched.
        rc = jnp.asarray(round_cap, jnp.int32)
        max_rounds = jnp.minimum(
            jnp.int32(max_rounds), jnp.where(rc > 0, rc, jnp.int32(max_rounds)))
    if doc_mask is None:
        doc_mask = jnp.ones((Q, N), jnp.bool_)
    a = jnp.where(doc_mask[:, :, None], a, 0.0).astype(jnp.float32)
    b = jnp.where(doc_mask[:, :, None], b, 0.0).astype(jnp.float32)

    if prereveal is not None:
        pr_flat = (prereveal & doc_mask[:, :, None]).reshape(Q * N, T)
        if carry is not None:
            # Prereveal seeds belong to the query ENTERING a slot; a
            # carried slot already absorbed its own at its fresh call.
            pr_flat = pr_flat & fresh_rows[:, None]
        pv_flat = jnp.where(
            pr_flat, prereveal_vals.reshape(Q * N, T).astype(jnp.float32),
            0.0)
        # Stage-1 seeds computed over a poisoned corpus row are non-finite
        # too — same quarantine treatment as a live reveal.
        pv_flat = jnp.where(jnp.isfinite(pv_flat), pv_flat, _QUAR)
    else:
        pr_flat = pv_flat = None

    q_doc_off = (jnp.arange(Q, dtype=jnp.int32) * N)[:, None]       # (Q, 1)

    # Per-query init split — same stream as run_batched_bandit's
    # ``key, k_init = split(key)`` so trajectories line up query by query.
    split2 = jax.vmap(lambda kk: tuple(jax.random.split(kk)))
    state_keys, k_init = split2(keys)
    if carry is not None:
        state_keys = jnp.where(fresh, state_keys, carry.key)

    # Init reveal (paper footnote 2): one random cell per doc, all queries
    # pooled into a single (Q*N, 1) reveal.
    t0 = jax.vmap(lambda kk: jax.random.randint(kk, (N,), 0, T))(k_init)
    all_docs = jnp.arange(Q * N, dtype=jnp.int32)
    flat_t0 = t0.reshape(Q * N, 1)

    iv_kwargs = dict(T=T, N=N, delta=cfg.delta, alpha_ef=cfg.alpha_ef,
                     c=cfg.radius_c, bias_kappa=cfg.bias_kappa)
    if alpha_scale is not None:
        # serfling_radius is LINEAR in alpha_ef (checked by the fidelity
        # tests), so a traced effective alpha is exact — and x * 1.0 is an
        # IEEE identity, so scale 1.0 stays bit-identical to the static
        # config value.
        iv_kwargs["alpha_ef"] = (jnp.float32(cfg.alpha_ef)
                                 * jnp.asarray(alpha_scale, jnp.float32))

    def sanitize(vals):
        """Finite-score guard on a block of freshly revealed cell values:
        identity on finite data, _QUAR where poisoned."""
        return jnp.where(jnp.isfinite(vals), vals, _QUAR)

    def get_intervals_q(n_q, total_q, total_sq_q, revealed_q, a_q, b_q,
                        mask_q) -> B.Intervals:
        iv = B.intervals(n_q, total_q, total_sq_q, revealed_q, a_q, b_q,
                         **iv_kwargs)
        return iv._replace(
            s_hat=jnp.where(mask_q, iv.s_hat, _NEG),
            lcb=jnp.where(mask_q, iv.lcb, _NEG),
            ucb=jnp.where(mask_q, iv.ucb, _NEG),
        )

    select_q = functools.partial(_round_select, k=k, epsilon=cfg.epsilon,
                                 half=half_w, G=G_cap)

    def select_round(st_key, iv, revealed_q, n_q, active, *, compact):
        """Shared round front-end: per-query LUCB selection, capacity
        allotment over both growth axes, and frontier pooling. Returns the
        raw selection (for key/stop bookkeeping), the pooled (doc, tok,
        cell) arrays, the per-query no-progress flags, and this round's
        frontier occupancy."""
        sel = jax.vmap(select_q)(st_key, iv, revealed_q, n_q, a, b, doc_mask)

        # Capacity allotment: freed DOC slots are split evenly among active
        # queries (never below the solo width, never above the selection
        # width), and remaining CELL capacity (F*G cells per round) widens
        # each surviving slot's token block — 2-D continuous batching.
        n_active = jnp.maximum(jnp.sum(active.astype(jnp.int32)), 1)
        per_group = jnp.clip(F // (2 * n_active), half, half_w)
        per_tok = jnp.clip((F * G) // (n_active * 2 * per_group), G, G_cap)
        grp_en = jnp.arange(half_w, dtype=jnp.int32) < per_group
        doc_en = jnp.concatenate([grp_en, grp_en])              # (W,)
        tok_en = jnp.arange(G_cap, dtype=jnp.int32) < per_tok   # (G_cap,)

        live = active & ~sel.stop                               # (Q,)
        sel_en = (sel.cell_ok & doc_en[None, :, None]
                  & tok_en[None, None, :])                      # (Q, W, G_cap)
        cell_en = sel_en & live[:, None, None]
        no_progress = ~jnp.any(sel_en, axis=(1, 2))

        flat_doc = (sel.doc_idx + q_doc_off).reshape(Q * W)
        flat_tok = sel.tok_idx.reshape(Q * W, G_cap)
        flat_cell = cell_en.reshape(Q * W, G_cap)
        slot_live = jnp.any(flat_cell, axis=-1)                 # (Q*W,)
        if compact:
            # Pool + compact: scatter live slots to the frontier front; the
            # overflow index F is dropped, so retired queries' slots vanish
            # and the launch batch stays at the fixed capacity F < Q*W.
            pos = jnp.cumsum(slot_live.astype(jnp.int32)) - 1
            dump = jnp.where(slot_live, pos, F)
            f_doc = jnp.zeros((F,), jnp.int32).at[dump].set(flat_doc,
                                                            mode="drop")
            f_tok = jnp.zeros((F, G_cap), jnp.int32).at[dump].set(
                flat_tok, mode="drop")
            f_cell = jnp.zeros((F, G_cap), jnp.bool_).at[dump].set(
                flat_cell, mode="drop")
        else:
            # No growth => capacity == selection width: feed the flat
            # selections straight to the launch (dead slots are masked
            # no-ops) and skip the cumsum + three compaction scatters.
            f_doc, f_tok, f_cell = flat_doc, flat_tok, flat_cell
        occ = jnp.sum(slot_live.astype(jnp.float32)) / jnp.float32(F)
        return sel, f_doc, f_tok, f_cell, no_progress, occ

    def finalize(n, total, total_sq, revealed, rounds, trips, occ_sum,
                 quar_doc):
        iv = jax.vmap(get_intervals_q)(
            n.reshape(Q, N), total.reshape(Q, N), total_sq.reshape(Q, N),
            revealed.reshape(Q, N, T), a, b, doc_mask)
        # Quarantined docs (any revealed cell tripped the finite-score
        # guard) are forced out of the top-K; identity when none did.
        quar_q = quar_doc.reshape(Q, N) & doc_mask
        iv = iv._replace(s_hat=jnp.where(quar_q, _NEG, iv.s_hat))
        tk = jax.vmap(functools.partial(_topk_mask, k=k))(iv.s_hat)
        topk_idx = tk[1]
        sep = jax.vmap(lambda iv_q, m_q: _select_arms(iv_q, _topk_mask(
            iv_q.s_hat, k)[0], m_q))(iv, doc_mask)
        separated = jax.vmap(
            lambda iv_q, ip, im: iv_q.lcb[ip] >= iv_q.ucb[im])(
            iv, sep[0], sep[1])

        rev_q = revealed.reshape(Q, N, T) & doc_mask[:, :, None]
        n_rev = jnp.sum(rev_q, axis=(1, 2))
        n_cells = jnp.maximum(jnp.sum(doc_mask, axis=1) * T, 1)
        total_rounds = jnp.sum(rounds)
        return PooledResult(
            topk=topk_idx,
            s_hat=iv.s_hat,
            coverage=n_rev.astype(jnp.float32) / n_cells.astype(jnp.float32),
            reveals=n_rev.astype(jnp.int32),
            rounds=rounds,
            separated=separated,
            revealed=rev_q,
            trips=trips,
            total_rounds=total_rounds,
            # Clamped: on a resumed slice, carried-in rounds can exceed
            # this slice's Q*trips budget.
            lockstep_waste=jnp.maximum(Q * trips - total_rounds, 0),
            occupancy=occ_sum / jnp.maximum(trips.astype(jnp.float32), 1.0),
            quarantined=jnp.sum(quar_q, axis=1).astype(jnp.int32),
        )

    def cond(loop_carry):
        st, trips, _ = loop_carry
        go = jnp.any((~st.done) & (st.rounds < max_rounds))
        if trip_limit > 0:
            go = jnp.logical_and(go, trips < trip_limit)
        return go

    # Queries with NO valid candidate start retired (rounds stay 0):
    # routine on a sharded corpus, where a query's candidates may all be
    # resident elsewhere — an empty query must not hold frontier slots
    # or inflate the per-shard round/occupancy accounting.
    done0 = ~jnp.any(doc_mask, axis=1)
    rounds0 = jnp.zeros((Q,), jnp.int32)
    if carry is not None:
        done0 = jnp.where(fresh, done0, carry.done)
        rounds0 = jnp.where(fresh, rounds0, carry.rounds)
    zero_trip = (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32))

    if fused:
        cells_fused = (compute_cells_fused if compute_cells_fused is not None
                       else _with_stats(compute_cells))
        flat_mask = doc_mask.reshape(Q * N)

        with jax.named_scope("frontier_init"):
            new0 = flat_mask[:, None]                           # (Q*N, 1)
            if carry is not None:
                new0 = new0 & fresh_rows[:, None]
            if pr_flat is not None:
                # An init cell that stage 1 already revealed is not new: it
                # must enter the stats exactly once (mirrors
                # _apply_block_reveal's ``already`` skip in the chain body).
                already0 = jnp.take_along_axis(pr_flat, flat_t0, axis=1)
                new0 = new0 & ~already0
            vals0, stats0 = cells_fused(all_docs,
                                        flat_t0 + (all_docs // N * T)[:, None],
                                        new0)
            # Finite-score guard: sanitize the revealed values and, for
            # rows where a non-finite value slipped into the in-kernel
            # statistic accumulation, rebuild that row's deltas from the
            # sanitized values. Rows with only finite cells keep the
            # kernel's own stats bit for bit (no re-summation =>
            # chain/fused parity untouched).
            bad0 = new0 & ~jnp.isfinite(vals0)
            vals0 = sanitize(vals0)
            vm0 = jnp.where(new0, vals0, 0.0)
            fix0 = jnp.stack([jnp.sum(new0.astype(jnp.float32), -1),
                              jnp.sum(vm0, -1), jnp.sum(vm0 * vm0, -1)],
                             axis=-1)
            stats0 = jnp.where(jnp.any(bad0, -1)[:, None], fix0, stats0)
            cellvals0 = jnp.where(flat_mask[:, None],
                                  jnp.full((Q * N, T), _UNREV), 0.0)
            if pr_flat is not None:
                cellvals0 = jnp.where(pr_flat, pv_flat, cellvals0)
                stats0 = stats0 + jnp.stack(
                    [jnp.sum(pr_flat, -1).astype(jnp.float32),
                     jnp.sum(pv_flat, -1), jnp.sum(pv_flat * pv_flat, -1)],
                    axis=-1)
            cellvals0 = cellvals0.at[all_docs[:, None], flat_t0].min(
                jnp.where(new0, vals0, _UNREV))
            if carry is not None:
                cellvals0 = jnp.where(fresh_rows[:, None], cellvals0,
                                      carry.cellvals)
                stats0 = jnp.where(fresh_rows[:, None], stats0, carry.stats)
            state = _FusedState(cellvals=cellvals0, stats=stats0,
                                key=state_keys, rounds=rounds0, done=done0)

        def body(carry):
            st, trips, occ_sum = carry
            active = (~st.done) & (st.rounds < max_rounds)       # (Q,)
            revealed = st.cellvals < _REV_THRESH                 # (Q*N, T)
            n_q = st.stats[:, 0].reshape(Q, N)
            iv = jax.vmap(get_intervals_q)(
                n_q, st.stats[:, 1].reshape(Q, N),
                st.stats[:, 2].reshape(Q, N), revealed.reshape(Q, N, T),
                a, b, doc_mask)
            sel, f_doc, f_tok, f_cell, no_progress, occ = select_round(
                st.key, iv, revealed.reshape(Q, N, T), n_q, active,
                compact=half_w > half)

            # ONE fused reveal launch + a two-scatter state update. No
            # already-revealed re-check here: the selection policy only
            # ever emits unrevealed cells (``_round_select`` masks width
            # and gumbel draws to _NEG on revealed cells and ``cell_ok``
            # thresholds them out), so ``f_cell`` IS the fresh-cell mask.
            # The chain oracle keeps the defensive re-check; the parity
            # tests (identical reveal counts and trajectories) pin that
            # the invariant holds.
            new = f_cell
            vals, dstats = cells_fused(
                f_doc, f_tok + (f_doc // N * T)[:, None], new)
            # Finite-score guard (same contract as the init reveal): only
            # rows that actually saw a non-finite value get their stat
            # deltas rebuilt from the sanitized values.
            bad = new & ~jnp.isfinite(vals)
            vals = sanitize(vals)
            vm = jnp.where(new, vals, 0.0)
            fix = jnp.stack([jnp.sum(new.astype(jnp.float32), -1),
                             jnp.sum(vm, -1), jnp.sum(vm * vm, -1)],
                            axis=-1)
            dstats = jnp.where(jnp.any(bad, -1)[:, None], fix, dstats)
            cellvals = st.cellvals.at[f_doc[:, None], f_tok].min(
                jnp.where(new, vals, _UNREV))
            stats = st.stats.at[f_doc].add(dstats)

            nxt = _FusedState(
                cellvals=cellvals, stats=stats, key=sel.key,
                rounds=st.rounds + active.astype(jnp.int32),
                done=st.done | (active & (sel.stop | no_progress)))
            return nxt, trips + 1, occ_sum + occ

        with jax.named_scope("frontier_round"):
            state, trips, occ_sum = jax.lax.while_loop(
                cond, body, (state, *zero_trip))
        res = finalize(state.stats[:, 0], state.stats[:, 1],
                       state.stats[:, 2], state.cellvals < _REV_THRESH,
                       state.rounds, trips, occ_sum,
                       jnp.any(state.cellvals <= _QUAR_THRESH, axis=-1))
        return (res, state) if return_state else res

    # ------------------------------------------------------------------
    # Chain round body — the REPRO_KERNEL_IMPL=ref oracle: abstract cell
    # gather + the classic five-scatter _apply_block_reveal update over a
    # stacked BanditState. Kept bit-identical to the pre-fusion engine.
    # ------------------------------------------------------------------
    state = BanditState(
        values=jnp.zeros((Q * N, T), jnp.float32),
        revealed=(~doc_mask[:, :, None]).reshape(Q * N, 1)
        & jnp.ones((Q * N, T), jnp.bool_),
        n=jnp.zeros((Q * N,), jnp.int32),
        total=jnp.zeros((Q * N,), jnp.float32),
        total_sq=jnp.zeros((Q * N,), jnp.float32),
        key=state_keys,                     # (Q,) keys — per-query streams
        rounds=rounds0,                     # per-query round counters
        done=done0,                         # per-query retirement flags
    )

    if carry is not None:
        # Unpack the sentinel encoding into the five-field BanditState for
        # carried rows (fresh rows keep the cold-start init above). The
        # encoding is lossless: revealed <=> cellvals below the sentinel
        # threshold, and unrevealed values are definitionally 0 here.
        c_rev = carry.cellvals < _REV_THRESH
        fr = fresh_rows[:, None]
        state = state._replace(
            values=jnp.where(fr, state.values,
                             jnp.where(c_rev, carry.cellvals, 0.0)),
            revealed=jnp.where(fr, state.revealed, c_rev),
            n=jnp.where(fresh_rows, state.n,
                        carry.stats[:, 0].astype(jnp.int32)),
            total=jnp.where(fresh_rows, state.total, carry.stats[:, 1]),
            total_sq=jnp.where(fresh_rows, state.total_sq,
                               carry.stats[:, 2]),
        )

    if pr_flat is not None:
        # Seed the statistics with the prerevealed cells; the init reveal
        # below then skips them via _apply_block_reveal's ``already`` check.
        state = state._replace(
            values=state.values + pv_flat,
            revealed=state.revealed | pr_flat,
            n=state.n + jnp.sum(pr_flat, -1).astype(jnp.int32),
            total=state.total + jnp.sum(pv_flat, -1),
            total_sq=state.total_sq + jnp.sum(pv_flat * pv_flat, -1))

    with jax.named_scope("frontier_init"):
        init_vals = sanitize(compute_cells(
            all_docs, flat_t0 + (all_docs // N * T)[:, None]))
        init_valid = doc_mask.reshape(Q * N, 1)
        if carry is not None:
            init_valid = init_valid & fresh_rows[:, None]
        state = _apply_block_reveal(state, all_docs, flat_t0, init_vals,
                                    init_valid)

    def per_query_intervals(st: BanditState) -> B.Intervals:
        return jax.vmap(get_intervals_q)(
            st.n.reshape(Q, N), st.total.reshape(Q, N),
            st.total_sq.reshape(Q, N), st.revealed.reshape(Q, N, T),
            a, b, doc_mask)

    def body(carry):
        st, trips, occ_sum = carry
        active = (~st.done) & (st.rounds < max_rounds)          # (Q,)

        iv = per_query_intervals(st)
        sel, f_doc, f_tok, f_cell, no_progress, occ = select_round(
            st.key, iv, st.revealed.reshape(Q, N, T), st.n.reshape(Q, N),
            active, compact=True)

        # ONE pooled reveal for the whole batch round, then the scatter
        # chain into the stacked statistics.
        vals = sanitize(compute_cells(f_doc, f_tok + (f_doc // N * T)[:, None]))
        nxt = _apply_block_reveal(st, f_doc, f_tok, vals, f_cell)

        # Per-query bookkeeping — mirrors the solo loop's cond/stop exactly:
        # a query that separates this round reveals nothing (its slots were
        # masked out of the frontier) and retires with rounds+1.
        nxt = nxt._replace(
            key=sel.key,
            rounds=st.rounds + active.astype(jnp.int32),
            done=st.done | (active & (sel.stop | no_progress)),
        )
        return nxt, trips + 1, occ_sum + occ

    with jax.named_scope("frontier_round"):
        state, trips, occ_sum = jax.lax.while_loop(
            cond, body, (state, *zero_trip))
    res = finalize(state.n, state.total, state.total_sq, state.revealed,
                   state.rounds, trips, occ_sum,
                   jnp.any(state.revealed & (state.values <= _QUAR_THRESH),
                           axis=-1))
    if return_state:
        # Pack back to the sentinel encoding — the shared slice boundary
        # format, so a stream may resume under either round body.
        packed = FrontierState(
            cellvals=jnp.where(state.revealed, state.values, _UNREV),
            stats=jnp.stack([state.n.astype(jnp.float32), state.total,
                             state.total_sq], axis=-1),
            key=state.key, rounds=state.rounds, done=state.done)
        return res, packed
    return res


def run_pooled_slice(
    compute_cells,
    a: jax.Array, b: jax.Array, keys: jax.Array, cfg: BatchedConfig,
    carry: FrontierState,
    fresh: jax.Array,
    *, trip_limit: int, **kw,
) -> tuple:
    """One bounded segment of the pooled bandit — the continuous-batching
    step. Resume from ``carry``, re-initialize the ``fresh`` slots from
    this call's ``a``/``b``/``keys`` (and ``prereveal``/``doc_mask`` via
    ``**kw``), run at most ``trip_limit`` global while_loop trips, and
    return ``(PooledResult, FrontierState)``. The host loop harvests slots
    whose returned ``state.done`` is set (their PooledResult rows are
    final), marks them fresh, and calls again — the other slots' bandit
    state rides through unchanged. Start a stream from
    :func:`init_frontier_state` with ``fresh`` all-True."""
    return run_pooled_bandit(compute_cells, a, b, keys, cfg, carry=carry,
                             fresh=fresh, trip_limit=trip_limit,
                             return_state=True, **kw)


def run_pooled_oracle(
    h_full: jax.Array, a: jax.Array, b: jax.Array, keys: jax.Array, *,
    fused: Optional[bool] = None, **kw,
) -> PooledResult:
    """Oracle-mode pooled engine: cells come from a precomputed (Q, N, T)
    H tensor. The flat token ids are mapped back to each slot's own query
    (doc q*N+i only ever pairs with tokens q*T+t), mirroring the stacked
    gather_maxsim contract. ``fused`` picks the round body (None = auto:
    fused unless REPRO_KERNEL_IMPL=ref); both bodies reveal identical
    cells.

    ``fused=None`` is resolved HERE, outside the jit boundary: were it a
    static arg resolved inside the trace, the compiled cache entry for
    ``None`` would pin whichever REPRO_KERNEL_IMPL was set at first call
    and silently serve the wrong round body after a same-process env
    change (the monkeypatch pattern the kernel tests rely on)."""
    return _pooled_oracle_jit(h_full, a, b, keys,
                              fused=_auto_fused() if fused is None
                              else fused, **kw)


@functools.partial(
    jax.jit,
    static_argnames=("k", "delta", "alpha_ef", "epsilon", "radius_c",
                     "block_docs", "block_tokens", "max_rounds",
                     "bias_kappa", "max_block_docs", "max_block_tokens",
                     "fused"),
)
def _pooled_oracle_jit(
    h_full: jax.Array, a: jax.Array, b: jax.Array, keys: jax.Array, *,
    k: int, fused: bool, delta: float = 0.01, alpha_ef: float = 0.3,
    epsilon: float = 0.1, radius_c: float = 1.0, bias_kappa: float = 0.0,
    block_docs: int = 8, block_tokens: int = 8, max_rounds: int = -1,
    max_block_docs: int = 0, max_block_tokens: int = 0,
    doc_mask: Optional[jax.Array] = None,
) -> PooledResult:
    Q, N, T = h_full.shape
    cfg = BatchedConfig(k=k, delta=delta, alpha_ef=alpha_ef, epsilon=epsilon,
                        radius_c=radius_c, bias_kappa=bias_kappa,
                        block_docs=block_docs, block_tokens=block_tokens,
                        max_rounds=max_rounds, max_block_docs=max_block_docs,
                        max_block_tokens=max_block_tokens)
    h_flat = h_full.reshape(Q * N, T)

    def cells(flat_doc: jax.Array, flat_tok: jax.Array) -> jax.Array:
        t_local = flat_tok - (flat_doc // N * T)[:, None]
        return h_flat[flat_doc[:, None], jnp.clip(t_local, 0, T - 1)]

    return run_pooled_bandit(cells, a, b, keys, cfg, doc_mask=doc_mask,
                             fused=fused)
