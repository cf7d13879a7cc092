"""Rerank step (``retrieval/service.py``): mean device time of one
execution of the step program (``jit_run`` in the trace's XLA Modules
line) in the traced window (ms)."""
import numpy as np

PROGRAM = "jit_run"


def read(run):
    if run.trace is None:
        return None
    t = run.trace.program_time(PROGRAM)
    return float(np.mean(t)) * 1e3 if t else None
