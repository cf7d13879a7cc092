"""Dense MaxSim Pallas TPU kernel — the exact-reranking hot spot (Eq. 4).

For every (doc i, query token t): H[i, t] = max_j <e_ij, q_t>.

Tiling (VMEM-resident, MXU-aligned):
  grid = (N/BN, T/BT, L/BL); the L axis is the innermost (sequential) grid
  dimension so a running max over document tokens lives in a VMEM scratch
  tile of shape (BN, BT) and the output block is written once, on the last
  L step. Embedding dim M is kept whole (128 in every assigned config — one
  MXU lane tile).

  per-step compute: (BN, BL, M) x (BT, M) -> dot_general batched over BN
  -> (BN, BL, BT) similarities -> masked max over BL -> running max.

Precision: every MaxSim-family dot runs at ``Precision.HIGHEST`` (f32
contract precision on the MXU). At Mosaic's default precision the scores
moved by up to 4.9e-4 relative against an f32 reference on a TPU v5e,
about what rounding the f32 query to bf16 gives; at HIGHEST the kernels
agree with the ``ref`` and interpret paths.

Block rule (Mosaic): the last two dims of every block divide by (8, 128)
or equal the array's, so BT is the whole T axis up to 128 and BL a multiple
of 128 (``kernels.tuning`` keeps its defaults and candidates inside it).

VMEM at defaults (BN=8, BT=128, BL=256, M=128, f32):
  E tile 1.0 MiB + Q tile 64 KiB + sims 1.0 MiB + scratch 4 KiB  << 16 MiB.

The helpers below (``corpus_specs``, ``load_tile``, ``mask_sims``) are
shared by every MaxSim-family kernel: one body serves the dense and the
quantized corpus, which differ only in how the embedding tile is loaded.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.quant import QuantTokens, dequant_block

_NEG = -3e38  # python float: jnp constants would be captured as kernel consts


def corpus_specs(doc_embs, payload_spec, row_spec):
    """(operands, in_specs) for a corpus tile: one dense array, or the int8
    payload plus its scale (and centroid-id + whole codebook) sidecars."""
    if not isinstance(doc_embs, QuantTokens):
        return [doc_embs], [payload_spec]
    ops, specs = [doc_embs.data, doc_embs.scales], [payload_spec, row_spec]
    if doc_embs.codes is not None:
        cb = doc_embs.codebook.shape
        ops += [doc_embs.codes, doc_embs.codebook]
        specs += [row_spec, pl.BlockSpec(cb, lambda *_: (0, 0))]
    return ops, specs


def load_tile(e_refs):
    """f32 embedding tile from the refs ``corpus_specs`` laid out; a
    quantized tile is reconstructed here, in VMEM, right before the dot."""
    if len(e_refs) == 1:
        return e_refs[0][...].astype(jnp.float32)
    return dequant_block(*(r[...] for r in e_refs))


def mask_sims(sims, mask):
    """Mask (..., BL, X) similarities with a (..., BL) token mask. The mask
    is widened to f32 before it gains its trailing axis: Mosaic cannot
    reshape an i1 vector (BL,) -> (BL, 1), and an f32 one it can."""
    keep = mask.astype(jnp.float32)[..., None] > 0.5
    return jnp.where(keep, sims, _NEG)


def _maxsim_kernel(*refs, n_e, n_l_blocks):
    e_refs = refs[:n_e]
    m_ref, q_ref, out_ref, acc_ref = refs[n_e:]
    l = pl.program_id(2)

    @pl.when(l == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, _NEG)

    e = load_tile(e_refs)                       # (BN, BL, M)
    q = q_ref[...].astype(jnp.float32)          # (BT, M)
    # (BN, BL, M) . (BT, M) -> (BN, BL, BT)
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
def maxsim(doc_embs: jax.Array, doc_tok_mask: jax.Array, queries: jax.Array,
           *, block_n: int = 8, block_t: int = 0, block_l: int = 256,
           interpret: bool = False) -> jax.Array:
    """Dense MaxSim matrix H (N, T). Shapes must be pre-padded so that
    BN | N, BT | T, BL | L (``repro.kernels.ops.maxsim_op`` handles padding).

    ``doc_embs`` may be a quantized corpus (``quant.QuantTokens``): the
    int8 payload and its sidecars are tiled through VMEM and dequantized
    in-kernel, so HBM only ever moves compressed bytes.
    """
    N, L, M = doc_embs.shape
    T = queries.shape[0]
    bn = min(block_n, N)
    bt = block_t if block_t > 0 else T
    bt = min(bt, T)
    bl = min(block_l, L)
    if N % bn or T % bt or L % bl:
        raise ValueError(f"maxsim blocks must tile the operands: "
                         f"(N,T,L)=({N},{T},{L}) vs (bn,bt,bl)="
                         f"({bn},{bt},{bl})")
    n_l_blocks = L // bl
    row = pl.BlockSpec((bn, bl), lambda i, j, l: (i, l))
    operands, in_specs = corpus_specs(
        doc_embs, pl.BlockSpec((bn, bl, M), lambda i, j, l: (i, l, 0)), row)
    return pl.pallas_call(
        functools.partial(_maxsim_kernel, n_e=len(operands),
                          n_l_blocks=n_l_blocks),
        grid=(N // bn, T // bt, n_l_blocks),
        in_specs=in_specs + [row, pl.BlockSpec((bt, M),
                                               lambda i, j, l: (j, 0))],
        out_specs=pl.BlockSpec((bn, bt), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((N, T), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bn, bt), jnp.float32)],
        interpret=interpret,
    )(*operands, doc_tok_mask, queries)
