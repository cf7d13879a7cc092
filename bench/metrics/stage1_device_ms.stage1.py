"""Stage-1 (``retrieval/ann.py``): mean device time of one execution of the
stage-1 program (``jit_stage1`` in the trace's XLA Modules line) in the
traced window (ms)."""
import numpy as np

PROGRAM = "jit_stage1"


def read(run):
    if run.trace is None:
        return None
    t = run.trace.program_time(PROGRAM)
    return float(np.mean(t)) * 1e3 if t else None
