"""Gathered MaxSim Pallas kernel — the block-synchronous bandit's reveal op.

Each round the bandit selects B ambiguous documents and G tokens per
document; the reveal computes exactly those B*G cells:

    out[b, g] = max_j <E[doc_idx[b], j], Q[tok_idx[b, g]]>

The doc/query gathers happen at the XLA level (cheap dynamic-slice / take on
small N); the kernel then runs a dense batched (B, L, M) x (B, G, M)
matmul-max with L tiled through VMEM. FLOPs = B * G * L * M * 2 exactly —
the bandit's savings are realized 1:1, with zero tile waste from irregular
reveal patterns. With a quantized corpus the gather moves int8 payload +
sidecars only; dequantization happens per VMEM block inside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.maxsim import _NEG, corpus_specs, load_tile, mask_sims
from repro.kernels.quant import corpus_take


def reveal_tile(e_refs, m_ref, q_ref, acc_ref, l):
    """One L step of a gathered reveal: (BB, BL, M) . (BB, G, M) batched
    over BB -> masked running max in the (BB, G) accumulator."""
    @pl.when(l == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, _NEG)

    e = load_tile(e_refs)                  # (BB, BL, M)
    q = q_ref[...].astype(jnp.float32)     # (BB, G, M)
    sims = jax.lax.dot_general(
        e, q, (((2,), (2,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    sims = mask_sims(sims, m_ref[...])
    acc_ref[...] = jnp.maximum(acc_ref[...], jnp.max(sims, axis=1))


def _gather_maxsim_kernel(*refs, n_e, n_l_blocks):
    m_ref, q_ref, out_ref, acc_ref = refs[n_e:]
    l = pl.program_id(1)
    reveal_tile(refs[:n_e], m_ref, q_ref, acc_ref, l)

    @pl.when(l == n_l_blocks - 1)
    def _done():
        out_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("block_b", "block_l",
                                             "interpret"))
def gather_maxsim(doc_embs: jax.Array, doc_tok_mask: jax.Array,
                  queries: jax.Array, doc_idx: jax.Array, tok_idx: jax.Array,
                  *, block_b: int = 8, block_l: int = 256,
                  interpret: bool = False) -> jax.Array:
    """out (B, G) — MaxSim values for the selected cells."""
    B, G = tok_idx.shape
    L, M = doc_embs.shape[1], doc_embs.shape[2]
    e = corpus_take(doc_embs, doc_idx, axis=0)         # (B, L, M)
    m = jnp.take(doc_tok_mask, doc_idx, axis=0)        # (B, L)
    q = jnp.take(queries, tok_idx, axis=0)             # (B, G, M)

    bb = min(block_b, B)
    bl = min(block_l, L)
    if B % bb != 0 or L % bl != 0:
        raise ValueError(
            f"gather_maxsim needs pre-padded shapes: B={B} must be a "
            f"multiple of block_b={bb} and L={L} of block_l={bl} — call it "
            "through repro.kernels.ops.gather_maxsim_op, which pads both "
            "axes (and documents the padding contract).")
    n_l_blocks = L // bl
    row = pl.BlockSpec((bb, bl), lambda i, l: (i, l))
    operands, in_specs = corpus_specs(
        e, pl.BlockSpec((bb, bl, M), lambda i, l: (i, l, 0)), row)
    return pl.pallas_call(
        functools.partial(_gather_maxsim_kernel, n_e=len(operands),
                          n_l_blocks=n_l_blocks),
        grid=(B // bb, n_l_blocks),
        in_specs=in_specs + [row, pl.BlockSpec((bb, G, M),
                                               lambda i, l: (i, 0, 0))],
        out_specs=pl.BlockSpec((bb, G), lambda i, l: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, G), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bb, G), jnp.float32)],
        interpret=interpret,
    )(*operands, m, q)
