"""Rerank step after stage-1 (``retrieval/service.py``): mean device time
of one execution of the step program (``jit_run``) in the traced window of
the stage-1 cell (ms): the bandit over each batch's stage-1 candidates."""
import numpy as np

PROGRAM = "jit_run"


def read(run):
    if run.trace is None:
        return None
    t = run.trace.program_time(PROGRAM)
    return float(np.mean(t)) * 1e3 if t else None
