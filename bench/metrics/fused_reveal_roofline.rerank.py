"""Kernels (``kernels/reveal.py``): the least time for the traced
``fused_reveal`` launches' operands and operations, from their shapes
(``bench/kernels/fused_reveal.py``), over the kernel's device time (%).
Memory bounds every launch at these sizes."""
from bench.kernels import fused_reveal
from bench.stats import least_time, share_pct


def read(run):
    if run.trace is None:
        return None
    least = took = 0.0
    for e in run.trace.op_events(fused_reveal.NAME):
        c = fused_reveal.cost(e.name)
        if c is None:
            return None
        least += least_time(*c, run.peaks)[0]
        took += e.dur / 1e9
    return share_pct(least, took)
