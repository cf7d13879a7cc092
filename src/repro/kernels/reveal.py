"""Fused reveal-round Pallas kernel — one launch per pooled bandit round.

The pooled frontier engine (``repro.core.frontier``) used to lower each
round through a CHAIN of XLA ops: gather the selected doc embeddings into
an (F, L, M) HBM buffer, launch ``gather_maxsim`` over it, then scatter the
(F, G) values back into the stacked statistics (values / revealed /
n / total / total_sq — five separate scatters). Every link in that chain is
an HBM round-trip, which is exactly what FLASH-MAXSIM-style IO analysis
says the late-interaction hot loop cannot afford.

This kernel fuses the gather -> score -> accumulate middle of the round:

  * the frontier's compacted doc selections (``doc_idx``) are SCALAR
    PREFETCHED, so each grid step DMAs the selected document's embedding
    tile straight from the corpus-resident (D, L, M) tensor into VMEM —
    the (F, L, M) gathered intermediate is never materialized in HBM;
  * MaxSim over the document axis runs with a VMEM-resident running max
    (L tiled through the innermost grid dimension);
  * the per-candidate sufficient statistics that ``core.bounds`` consumes
    are accumulated IN the kernel: for every frontier row the output
    carries [reveal-count delta, revealed-sum delta, revealed-sum-of-
    squares delta] over the freshly revealed cells (``new_mask``), so the
    caller's state update shrinks to one scatter-min (cell values) plus
    one 3-column scatter-add.

Grid: (F // block_b, L // block_l), L innermost. ``gather=True`` requires
``block_b == 1`` (one frontier row per step — the index map can only
redirect a whole block): only the embedding tile is gathered in-kernel,
while the small per-row operands (token mask, quantization sidecars) are
gathered by XLA into (F, 1, X) arrays whose squeezed (1, X) blocks meet
Mosaic's (8, 128)-or-whole-dim block rule. ``gather=False`` takes
pre-gathered (F, L, M) rows and allows wider row blocks, which is the
cheaper layout for the interpret-mode CI lane (trace time scales with grid
size, and CPU has no HBM/VMEM distinction to exploit).

Stats live in the first ``STATS_USED`` lanes of a ``STATS_W``-wide output
row (lane-padded so the store stays tile-aligned on TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gather_maxsim import reveal_tile
from repro.kernels.maxsim import corpus_specs

STATS_W = 8        # lane-padded stats row width
STATS_USED = 3     # [d_count, d_total, d_total_sq]


def _fused_reveal_kernel(doc_idx_ref, *refs, n_e, n_l_blocks):
    del doc_idx_ref  # consumed by the index maps, not the body
    m_ref, q_ref, new_ref, vals_ref, stats_ref, acc_ref = refs[n_e:]
    l = pl.program_id(1)
    reveal_tile(refs[:n_e], m_ref, q_ref, acc_ref, l)

    @pl.when(l == n_l_blocks - 1)
    def _done():
        v = acc_ref[...]                   # (BB, G)
        vals_ref[...] = v
        new = new_ref[...]                 # (BB, G) bool — fresh cells only
        nf = new.astype(jnp.float32)
        vm = jnp.where(new, v, 0.0)
        d_n = jnp.sum(nf, axis=-1)         # (BB,)
        d_tot = jnp.sum(vm, axis=-1)
        # vm * v (not nf * v * v): a 0 * inf from an all-masked document's
        # _NEG sentinel squaring out of f32 range would poison the row
        # with NaN; where-masking first keeps dead lanes exactly 0.
        d_sq = jnp.sum(vm * v, axis=-1)
        lane = jax.lax.broadcasted_iota(jnp.int32, (v.shape[0], STATS_W), 1)
        stats_ref[...] = jnp.where(
            lane == 0, d_n[:, None],
            jnp.where(lane == 1, d_tot[:, None],
                      jnp.where(lane == 2, d_sq[:, None], 0.0)))


@functools.partial(jax.jit, static_argnames=("block_b", "block_l", "gather",
                                             "interpret"))
def fused_reveal(doc_embs: jax.Array, doc_tok_mask: jax.Array,
                 q_sel: jax.Array, new_mask: jax.Array, doc_idx: jax.Array,
                 *, block_b: int = 1, block_l: int = 256,
                 gather: bool = True, interpret: bool = False):
    """One fused reveal round.

    doc_embs:     (D, L, M) corpus/stacked docs (``gather=True``) or the
                  pre-gathered (F, L, M) frontier rows (``gather=False``);
                  may be a quantized corpus (``quant.QuantTokens``), in
                  which case each grid step DMAs the compressed payload
                  block and dequantizes it in VMEM
    doc_tok_mask: matching (D, L) / (F, L) token validity
    q_sel:        (F, G, M) pre-gathered query tokens per frontier row
    new_mask:     (F, G) bool — cells that are fresh this round
    doc_idx:      (F,) i32 — selected doc per frontier row (scalar-prefetch
                  gather target when ``gather=True``; still threaded when
                  ``gather=False`` so both modes share one call signature)
    returns:      vals (F, G) f32 MaxSim values,
                  stats (F, STATS_W) f32 with lanes [dn, dtotal, dtotal_sq]
    """
    F, G, M = q_sel.shape
    L = doc_embs.shape[1]
    bb = 1 if gather else min(block_b, max(F, 1))
    bl = min(block_l, max(L, 1))
    if F % bb != 0 or L % bl != 0:
        raise ValueError(
            f"fused_reveal needs pre-padded shapes: F={F} must be a "
            f"multiple of block_b={bb} and L={L} of block_l={bl} — call it "
            "through repro.kernels.ops.fused_reveal_op, which pads both "
            "axes (and documents the padding contract).")
    n_l_blocks = L // bl
    q_spec = pl.BlockSpec((bb, G, M), lambda i, l, di: (i, 0, 0))
    if gather:
        # Row operands become (F, 1, X): a squeezed leading block dim leaves
        # the kernel (1, X) tiles whose last two dims equal the array's.
        sq = pl.squeezed
        unit = lambda x: x.reshape(x.shape[0], 1, x.shape[-1])
        sel = lambda x: unit(jnp.take(x, doc_idx, axis=0))
        row = pl.BlockSpec((sq, 1, bl), lambda i, l, di: (i, 0, l))
        cell = pl.BlockSpec((sq, 1, G), lambda i, l, di: (i, 0, 0))
        stat = pl.BlockSpec((sq, 1, STATS_W), lambda i, l, di: (i, 0, 0))
        operands, in_specs = corpus_specs(
            doc_embs, pl.BlockSpec((1, bl, M), lambda i, l, di: (di[i], l, 0)),
            row)
        operands[1:3] = [sel(x) for x in operands[1:3]]  # scales, codes
        operands += [sel(doc_tok_mask), q_sel, unit(new_mask)]
        out_shape = [jax.ShapeDtypeStruct((F, 1, G), jnp.float32),
                     jax.ShapeDtypeStruct((F, 1, STATS_W), jnp.float32)]
    else:
        row = pl.BlockSpec((bb, bl), lambda i, l, di: (i, l))
        cell = pl.BlockSpec((bb, G), lambda i, l, di: (i, 0))
        stat = pl.BlockSpec((bb, STATS_W), lambda i, l, di: (i, 0))
        operands, in_specs = corpus_specs(
            doc_embs, pl.BlockSpec((bb, bl, M), lambda i, l, di: (i, l, 0)),
            row)
        operands += [doc_tok_mask, q_sel, new_mask]
        out_shape = [jax.ShapeDtypeStruct((F, G), jnp.float32),
                     jax.ShapeDtypeStruct((F, STATS_W), jnp.float32)]
    n_e = len(operands) - 3
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(F // bb, n_l_blocks),
        in_specs=in_specs + [row, q_spec, cell],
        out_specs=[cell, stat],
        scratch_shapes=[pltpu.VMEM((bb, G), jnp.float32)],
    )
    vals, stats = pl.pallas_call(
        functools.partial(_fused_reveal_kernel, n_e=n_e,
                          n_l_blocks=n_l_blocks),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="fused_reveal",
    )(doc_idx, *operands)
    return vals.reshape(F, G), stats.reshape(F, STATS_W)
