"""Stage-1: the least time for one batch's scan (every real query token
against every token row of the index: 2 B T C L M operations at the bf16
peak, or the index read once at the HBM rate, whichever is longer;
``bench/kernels/stage1.py``), over the stage-1 program's device time (%)."""
import numpy as np

from bench.kernels import stage1
from bench.stats import least_time, share_pct

PROGRAM = "jit_stage1"


def read(run):
    if run.trace is None or not run.batches:
        return None
    took = run.trace.program_time(PROGRAM)
    if not took:
        return None
    C, L, M = run.corpus_shape
    B = float(np.mean([b.n_real for b in run.batches]))
    flops, nbytes = stage1.cost(B, run.cfg["query_tokens"], C, L, M,
                                run.itemsize)
    least, _ = least_time(flops, nbytes, run.peaks)
    return share_pct(least, float(np.mean(took)))
