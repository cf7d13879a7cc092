"""Necessary work of one stage-1 batch: every query token against every
token row of the index.

* operations: 2 * B * T * C * L * M;
* bytes: the resident index read once, C * L * M elements of its type.
"""
from __future__ import annotations

from typing import Tuple


def cost(B: int, T: int, C: int, L: int, M: int,
         itemsize: int = 2) -> Tuple[float, float]:
    return 2.0 * B * T * C * L * M, float(C * L * M * itemsize)
