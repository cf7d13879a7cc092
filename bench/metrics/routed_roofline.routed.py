"""Routed step: the least time for one chip's stage-1 scan of a batch over
its own block of ``C / chips`` passages (every real query token against
every token row there: 2 B T C L M / chips operations at the bf16 peak, or
the block read once at the HBM rate, whichever is longer;
``bench/kernels/stage1.py``), over the routed program's device time per
execution (``routed_device_ms.routed``, %)."""
import numpy as np

from bench.kernels import stage1
from bench.routed import whole_runs
from bench.stats import least_time, share_pct


def read(run):
    if run.trace is None or not run.batches:
        return None
    runs = whole_runs(run.trace)
    if not runs:
        return None
    C, L, M = run.corpus_shape
    B = float(np.mean([b.n_real for b in run.batches]))
    flops, nbytes = stage1.cost(B, run.cfg["query_tokens"], C // run.chips,
                                L, M, run.itemsize)
    least, _ = least_time(flops, nbytes, run.peaks)
    return share_pct(least, float(np.mean([e.dur for e in runs])) / 1e9)
