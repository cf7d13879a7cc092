"""Necessary work of one rerank batch, whatever path computes it.

* operations: every revealed (document, query token) cell costs one
  multiply-add per token pair of the document: 2 * L * M;
* bytes: every real candidate's L * M rows read once, at the resident
  type's width.
"""
from __future__ import annotations

from typing import Tuple


def cost(candidates: float, revealed_cells: float, L: int, M: int,
         itemsize: int = 2) -> Tuple[float, float]:
    return 2.0 * revealed_cells * L * M, float(candidates * L * M * itemsize)
