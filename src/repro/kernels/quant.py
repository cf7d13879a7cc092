"""Compressed resident-corpus formats: int8 rows and centroid residuals.

The corpus-per-device ceiling is set by resident bytes, and ColBERTv2-style
compression (PAPERS.md) shows late-interaction embeddings survive centroid
id + low-bit residual with negligible quality loss.  This module defines the
quantized corpus container and the host-side encoders; the kernels under
``repro.kernels`` dequantize blocks *in VMEM* right before the f32 MaxSim
accumulation, so the reconstructed rows never touch HBM (the FLASH-MAXSIM
IO argument, extended one step down the memory hierarchy).

Formats (``CORPUS_FORMATS``):

  * ``bf16``     — uncompressed passthrough: the corpus stays a plain array
                   at its source residency (bf16 in, bf16 resident; f32 in,
                   f32 resident — the pre-compression behavior, bit-exact).
  * ``int8``     — per-(doc, token)-row symmetric quantization: for each
                   length-M row, scale = absmax/127 (stored bf16), payload
                   int8.  ~M + 2 bytes/row vs 4M uncompressed.
  * ``residual`` — centroid id + int8 residual: each row is assigned its
                   nearest codebook centroid (the stage-1 router's spherical
                   k-means centroids double as the codebook) and only the
                   residual is int8-quantized.  Decoded row =
                   codebook[code] + data * scale.

``QuantTokens`` is a NamedTuple, hence automatically a jax pytree: it flows
through ``jit`` / ``vmap`` / ``shard_map`` wherever a plain corpus array
did, and ``.shape`` / ``.dtype`` / ``.ndim`` delegate to the int8 payload so
shape-derived call sites (``corpus.shape[2]`` etc.) keep working unchanged.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

CORPUS_FORMATS = ("bf16", "int8", "residual")

# int8 symmetric range. 127 (not 128) keeps the code range symmetric so
# dequantization has no bias term.
_QMAX = 127.0


class QuantTokens(NamedTuple):
    """A quantized token-embedding tensor with payload shape (..., L, M).

    data:     int8 (..., L, M) quantized rows (or residuals)
    scales:   (..., L) per-row dequant scale, bf16-resident
    codes:    (..., L) i32 centroid id per row — residual format only
    codebook: (Kc, M) f32 shared codebook — residual format only, replicated
              (never sharded or reshaped with the doc axis)
    """
    data: Any
    scales: Any
    codes: Any = None
    codebook: Any = None

    @property
    def shape(self):
        return self.data.shape

    @property
    def ndim(self):
        return self.data.ndim

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def fmt(self) -> str:
        return "residual" if self.codes is not None else "int8"


def corpus_format(x) -> str:
    """Format tag of a corpus operand (plain array -> 'bf16')."""
    return x.fmt if isinstance(x, QuantTokens) else "bf16"


def format_ordinal(fmt: str) -> int:
    """Power-of-two ordinal used to key tuning buckets per format."""
    if fmt not in CORPUS_FORMATS:
        raise ValueError(f"unknown corpus format {fmt!r}; "
                         f"expected one of {CORPUS_FORMATS}")
    return 1 << CORPUS_FORMATS.index(fmt)


def corpus_nbytes(x) -> int:
    """Resident bytes of a corpus operand, counting every quantization
    sidecar (scales, codes, codebook) — the honest bytes/doc numerator."""
    if isinstance(x, QuantTokens):
        leaves = [x.data, x.scales, x.codes, x.codebook]
        return sum(int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
                   for a in leaves if a is not None)
    return int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize


# ---------------------------------------------------------------------------
# host-side encoders (numpy: corpus build happens before device placement)
# ---------------------------------------------------------------------------

def _encode_rows(x: np.ndarray, scale_dtype) -> "tuple[np.ndarray, np.ndarray]":
    """Symmetric per-row int8 encode of (..., M) rows -> (int8, scales)."""
    absmax = np.max(np.abs(x), axis=-1)
    scale = (absmax / _QMAX).astype(np.float32)
    # bf16 scale residency: round the scale FIRST, then quantize against the
    # rounded value — the pair (data, scale) is self-consistent, so the
    # round-trip error stays bounded by scale/2 per element.
    scale = np.asarray(jnp.asarray(scale).astype(scale_dtype))
    s32 = scale.astype(np.float32)
    safe = np.where(s32 > 0, s32, 1.0)
    data = np.clip(np.rint(x / safe[..., None]), -_QMAX, _QMAX).astype(np.int8)
    return data, scale


def quantize_int8(embs, scale_dtype=jnp.bfloat16) -> QuantTokens:
    """Per-(doc, token)-row symmetric int8 quantization (host-side).

    All-zero rows get scale 0 and decode to exact zeros; rows with absmax
    anywhere up to f32 max are safe (scale = absmax/127 never overflows).
    """
    x = np.asarray(embs, dtype=np.float32)
    data, scale = _encode_rows(x, scale_dtype)
    return QuantTokens(data=data, scales=scale)


def quantize_residual(embs, codebook, scale_dtype=jnp.bfloat16) -> QuantTokens:
    """Centroid id + int8 residual against a shared (Kc, M) codebook.

    The codebook is the stage-1 router's spherical-k-means centroids
    (unit rows); assignment is by max inner product, matching the router's
    affinity metric.
    """
    x = np.asarray(embs, dtype=np.float32)
    cb = np.asarray(codebook, dtype=np.float32)
    if cb.ndim != 2 or cb.shape[0] < 1 or cb.shape[1] != x.shape[-1]:
        raise ValueError(f"codebook must be (Kc, M={x.shape[-1]}); "
                         f"got {cb.shape}")
    codes = np.argmax(x @ cb.T, axis=-1).astype(np.int32)
    resid = x - cb[codes]
    data, scale = _encode_rows(resid, scale_dtype)
    return QuantTokens(data=data, scales=scale, codes=codes, codebook=cb)


def quantize(embs, fmt: str, codebook=None,
             scale_dtype=jnp.bfloat16):
    """Encode ``embs`` into ``fmt`` ('bf16' passes through unchanged)."""
    if fmt == "bf16":
        return embs
    if fmt == "int8":
        return quantize_int8(embs, scale_dtype=scale_dtype)
    if fmt == "residual":
        if codebook is None:
            raise ValueError("residual format needs a (Kc, M) codebook "
                             "(the stage-1 router centroids)")
        return quantize_residual(embs, codebook, scale_dtype=scale_dtype)
    raise ValueError(f"unknown corpus format {fmt!r}; "
                     f"expected one of {CORPUS_FORMATS}")


# ---------------------------------------------------------------------------
# dequantization — the same math the kernels run per VMEM block
# ---------------------------------------------------------------------------

def dequant_block(data, scales, codes=None, codebook=None):
    """Reconstruct f32 rows from quantized operands; jnp-only so it runs
    unchanged inside a Pallas kernel body (on a VMEM block) and in the
    reference oracles (on whole arrays).

    The codebook gather is expressed as a one-hot matmul, which lowers to
    an MXU dot on TPU instead of a serialized VMEM gather (Kc is small —
    the codebook tile is resident anyway).
    """
    out = data.astype(jnp.float32) * scales.astype(jnp.float32)[..., None]
    if codes is not None:
        kc = codebook.shape[0]
        one_hot = (codes[..., None] == jnp.arange(kc, dtype=codes.dtype)
                   ).astype(jnp.float32)
        cents = jax.lax.dot_general(
            one_hot, codebook.astype(jnp.float32),
            (((one_hot.ndim - 1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        out = out + cents
    return out


def dequantize(qt: QuantTokens):
    """Full-array f32 reconstruction (oracle / ref-impl path)."""
    return dequant_block(qt.data, qt.scales, qt.codes, qt.codebook)


# ---------------------------------------------------------------------------
# structural helpers: treat (array | QuantTokens) uniformly at call sites
# ---------------------------------------------------------------------------

def corpus_take(x, idx, axis: int = 0):
    """``jnp.take`` over the doc axis of a corpus operand. The codebook is
    shared state, never gathered."""
    if isinstance(x, QuantTokens):
        return QuantTokens(
            data=jnp.take(x.data, idx, axis=axis),
            scales=jnp.take(x.scales, idx, axis=axis),
            codes=None if x.codes is None else jnp.take(x.codes, idx,
                                                        axis=axis),
            codebook=x.codebook)
    return jnp.take(x, idx, axis=axis)


def corpus_reshape(x, *lead: int):
    """Reshape the leading (doc/batch) axes to ``lead``, keeping each
    leaf's trailing dims: data (..., L, M), scales/codes (..., L)."""
    if isinstance(x, QuantTokens):
        l_dim, m_dim = x.data.shape[-2:]
        return QuantTokens(
            data=x.data.reshape(*lead, l_dim, m_dim),
            scales=x.scales.reshape(*lead, l_dim),
            codes=None if x.codes is None else x.codes.reshape(*lead, l_dim),
            codebook=x.codebook)
    return x.reshape(*lead, *x.shape[-2:])


def corpus_index(x, idx):
    """``x[idx]`` over the leading axis (codebook untouched)."""
    if isinstance(x, QuantTokens):
        return QuantTokens(
            data=x.data[idx], scales=x.scales[idx],
            codes=None if x.codes is None else x.codes[idx],
            codebook=x.codebook)
    return x[idx]


def _pad_axis(a, axis: int, mult: int, value=0):
    size = a.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, rem)
    return jnp.pad(a, widths, constant_values=value)


def corpus_pad_to(x, axis: int, mult: int, value=0):
    """Pad one axis of a corpus operand to a multiple of ``mult``.  Axis
    indices refer to the payload layout (..., L, M); the M axis only exists
    on the payload, every other axis is shared with scales/codes.  Pad rows
    get scale 0 / code 0, decoding to exact zeros (int8) or centroid 0
    (residual) — both are neutralized by the all-False pad token mask the
    callers maintain, same as zero pad rows on the dense path."""
    if not isinstance(x, QuantTokens):
        return _pad_axis(x, axis, mult, value)
    nd = x.data.ndim
    axis = axis % nd
    data = _pad_axis(x.data, axis, mult, value)
    if axis == nd - 1:                      # M axis: payload-only
        return x._replace(data=data)
    return QuantTokens(
        data=data,
        scales=_pad_axis(x.scales, axis, mult, 0),
        codes=None if x.codes is None else _pad_axis(x.codes, axis, mult, 0),
        codebook=x.codebook)


def corpus_asarray(x, as_numpy: bool = False):
    """np/jnp-ify every leaf (codebook included), preserving structure."""
    conv = np.asarray if as_numpy else jnp.asarray
    if isinstance(x, QuantTokens):
        return QuantTokens(
            data=conv(x.data), scales=conv(x.scales),
            codes=None if x.codes is None else conv(x.codes),
            codebook=None if x.codebook is None else conv(x.codebook))
    return conv(x)


def corpus_leaves(x) -> Sequence[Any]:
    """Non-None leaves of a corpus operand (plain array -> [array])."""
    if isinstance(x, QuantTokens):
        return [a for a in (x.data, x.scales, x.codes, x.codebook)
                if a is not None]
    return [x]
