"""Rerank step: mean over the window's batches of ``t_ready -
t_dispatched`` (ms): from the launch until the dispatch thread holds the
finished results, the wait behind the batch queued ahead on the device
and the step itself."""
from bench.stages import mean_stage_ms


def read(run):
    return mean_stage_ms(run, "t_dispatched", "t_ready")
