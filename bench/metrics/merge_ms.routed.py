"""Merge (``retrieval/service.py``, ``_merge_scorecards`` and the two
``psum``s, scope ``routed_merge``): mean device time per routed execution
on one chip of its collective operations (all-gather, all-reduce and any
reduce-scatter, collective-permute or all-to-all, with the -start/-done
halves of async ones), in the executions the trace holds whole (ms). On a
chip whose shard finishes first this includes waiting for the slowest
shard."""
import numpy as np

from bench.routed import collective_s, whole_runs


def read(run):
    if run.trace is None:
        return None
    runs = whole_runs(run.trace)
    if not runs:
        return None
    return float(np.mean(collective_s(run.trace, runs))) * 1e3
