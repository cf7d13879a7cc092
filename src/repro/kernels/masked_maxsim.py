"""Tile-masked MaxSim Pallas kernel — Col-Bandit's pruning made physical.

The bandit decides which (doc, query-token) tiles are worth computing; this
kernel SKIPS the MXU work for every inactive tile via ``pl.when`` — compute
is saved at tile granularity, not just masked out. Inactive output tiles are
written as exact 0 on the first L step so the output is fully defined.

tile_mask has shape (N/BN, T/BT): one bool per output tile, scalar-
prefetched into SMEM. The output is produced as (T/BT, N, BT) — each block's
last two dims (BN, BT) then meet Mosaic's (8, 128)-or-whole-dim rule even
for a narrow semantic BT — and transposed back to (N, T) by XLA. The
static-budget baselines (Doc-TopMargin with tile-aligned reveals) and the
bulk reranking path use this; the round-based bandit uses the gather kernel
instead (``gather_maxsim``) because its reveal sets are irregular.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.maxsim import _NEG, corpus_specs, load_tile, mask_sims


def _masked_maxsim_kernel(tm_ref, *refs, n_e, n_l_blocks):
    e_refs = refs[:n_e]
    m_ref, q_ref, out_ref, acc_ref = refs[n_e:]
    l = pl.program_id(2)
    active = tm_ref[pl.program_id(0), pl.program_id(1)] != 0

    @pl.when(jnp.logical_not(active) & (l == 0))
    def _skip():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(active)
    def _compute():
        @pl.when(l == 0)
        def _init():
            acc_ref[...] = jnp.full_like(acc_ref, _NEG)

        e = load_tile(e_refs)
        q = q_ref[...].astype(jnp.float32)
        sims = jax.lax.dot_general(
            e, q, (((2,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        sims = mask_sims(sims, m_ref[...])
        acc_ref[...] = jnp.maximum(acc_ref[...], jnp.max(sims, axis=1))

        @pl.when(l == n_l_blocks - 1)
        def _done():
            out_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("block_n", "block_t", "block_l",
                                             "interpret"))
def masked_maxsim(doc_embs: jax.Array, doc_tok_mask: jax.Array,
                  queries: jax.Array, tile_mask: jax.Array, *,
                  block_n: int = 8, block_t: int = 0, block_l: int = 256,
                  interpret: bool = False) -> jax.Array:
    N, L, M = doc_embs.shape
    T = queries.shape[0]
    bn = min(block_n, N)
    bt = block_t if block_t > 0 else T
    bt = min(bt, T)
    bl = min(block_l, L)
    if N % bn or T % bt or L % bl:
        raise ValueError(f"masked_maxsim blocks must tile the operands: "
                         f"(N,T,L)=({N},{T},{L}) vs (bn,bt,bl)="
                         f"({bn},{bt},{bl})")
    if tile_mask.shape != (N // bn, T // bt):
        raise ValueError(f"tile_mask must be (N//bn, T//bt)="
                         f"({N // bn},{T // bt}); got {tile_mask.shape}")
    n_l_blocks = L // bl
    row = pl.BlockSpec((bn, bl), lambda i, j, l, tm: (i, l))
    operands, in_specs = corpus_specs(
        doc_embs, pl.BlockSpec((bn, bl, M), lambda i, j, l, tm: (i, l, 0)),
        row)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(N // bn, T // bt, n_l_blocks),
        in_specs=in_specs + [row, pl.BlockSpec((bt, M),
                                               lambda i, j, l, tm: (j, 0))],
        out_specs=pl.BlockSpec((pl.squeezed, bn, bt),
                               lambda i, j, l, tm: (j, i, 0)),
        scratch_shapes=[pltpu.VMEM((bn, bt), jnp.float32)],
    )
    h = pl.pallas_call(
        functools.partial(_masked_maxsim_kernel, n_e=len(operands),
                          n_l_blocks=n_l_blocks),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T // bt, N, bt), jnp.float32),
        interpret=interpret,
    )(tile_mask.astype(jnp.int32), *operands, doc_tok_mask, queries)
    return jnp.transpose(h, (1, 0, 2)).reshape(N, T)
