"""Routed step (``retrieval/service.py``, ``make_routed_serving_step``,
program ``jit_step``): mean device time of one execution on one chip in the
traced window (ms): routing, the chip's stage-1 over its own block, the
bandit and the merge. Executions cut by the trace's edges are left out
(``bench/routed.py``)."""
import numpy as np

from bench.routed import whole_runs


def read(run):
    if run.trace is None:
        return None
    runs = whole_runs(run.trace)
    return float(np.mean([e.dur for e in runs])) / 1e6 if runs else None
