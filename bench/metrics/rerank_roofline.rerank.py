"""Rerank step: the least time the chip needs for a batch's necessary
work, over the step program's device time per batch (%). The work is the
same whatever path computes it (``bench/kernels/rerank.py``): every real
candidate's rows read once at the resident width, and 2 L M operations
per revealed cell, for a batch of the mean real size of the window's
batches. Memory bounds it at these sizes."""
import numpy as np

from bench.kernels import rerank
from bench.stats import least_time, share_pct

PROGRAM = "jit_run"


def read(run):
    if run.trace is None or not run.batches:
        return None
    took = run.trace.program_time(PROGRAM)
    if not took:
        return None
    _, L, M = run.corpus_shape
    n = cands = cells = 0.0
    for s in run.window.measured:
        if s.failed or run.templates[s.template].cand_ids is None:
            continue
        c = len(run.templates[s.template].cand_ids)
        n += 1
        cands += c
        cells += s.completion.reveal_fraction * c * s.completion.bucket[0]
    if not n:
        return None
    per_batch = float(np.mean([b.n_real for b in run.batches])) / n
    flops, nbytes = rerank.cost(cands * per_batch, cells * per_batch, L, M,
                                run.itemsize)
    least, _ = least_time(flops, nbytes, run.peaks)
    return share_pct(least, float(np.mean(took)))
