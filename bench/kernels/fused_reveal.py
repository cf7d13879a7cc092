"""Operations and bytes of one ``fused_reveal`` launch, from its shapes.

The launch is read from the trace's instruction text, e.g.
``%fused_reveal.13 = (f32[64,1,8]{...}, f32[64,1,8]{...}) custom-call(
s32[64]{...} %idx, bf16[2560,128,128]{...} %docs, s32[64,1,128]{...} %mask,
f32[64,8,128]{...} %q, s32[64,1,8]{...} %new), custom_call_target=...``:
F frontier rows, each scoring G query tokens against one document of L
tokens of width M.

* operations: 2 * F * G * L * M (one multiply-add per token pair);
* bytes: what a launch must move at least: the F selected document rows
  (F * L * M elements of the resident type, gathered in the kernel; the
  other rows of the stacked operand are not read), and every other
  operand and both outputs whole.
"""
from __future__ import annotations

import re
from typing import List, Optional, Tuple

NAME = "fused_reveal"
_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|f64)"
                    r"\[([\d,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
          "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "f64": 8}


def shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    return [(t, tuple(int(x) for x in dims.split(",") if x))
            for t, dims in _SHAPE.findall(text)]


def _nbytes(dtype: str, dims: Tuple[int, ...]) -> int:
    n = _BYTES[dtype]
    for d in dims:
        n *= d
    return n


def cost(text: str) -> Optional[Tuple[float, float]]:
    """(operations, bytes) of the launch the instruction text describes;
    None if the text does not hold its operands."""
    if " custom-call(" not in text:
        return None
    outs_text, args_text = text.split(" custom-call(", 1)
    args_text = args_text.split("), custom_call_target", 1)[0]
    outs, args = shapes(outs_text), shapes(args_text)
    # Operands: the row index (scalar prefetch), the documents, then the
    # per-row mask, the query tokens and the fresh-cell mask.
    if not outs or len(args) < 2 or len(args[1][1]) != 3:
        return None
    F, G = outs[0][1][0], outs[0][1][-1]
    dtype, (_, L, M) = args[1]
    flops = 2.0 * F * G * L * M
    moved = F * L * M * _BYTES[dtype]
    moved += sum(_nbytes(t, s) for i, (t, s) in enumerate(args) if i != 1)
    moved += sum(_nbytes(t, s) for t, s in outs)
    return flops, float(moved)
