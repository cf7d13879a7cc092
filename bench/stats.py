"""Arithmetic the metric readers share."""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np


def percentile_ms(seconds: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile of ``seconds`` in ms; None if empty or infinite
    (a failed request counts as infinitely late)."""
    a = np.asarray(list(seconds), np.float64)
    if not a.size:
        return None
    v = float(np.percentile(a, q)) * 1e3
    return v if np.isfinite(v) else None


def least_time(flops: float, nbytes: float, peaks: dict) -> Tuple[float, str]:
    """The least time the chip could take for the work, and which of its
    peaks bounds it: bf16 operations or HBM bytes."""
    t_flops = flops / peaks["bf16_flops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")


def share_pct(least_s: float, took_s: float) -> Optional[float]:
    """A roofline share in %, None where nothing was measured."""
    if took_s <= 0 or least_s <= 0:
        return None
    return 100.0 * least_s / took_s
