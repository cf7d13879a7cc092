"""Admission and batching (``serve/engine.py``): mean over the window's
batches of ``t_dispatched - t_prepared`` (ms): the offer to the bounded
dispatch queue, the wait in it, and the launch call."""
from bench.stages import mean_stage_ms


def read(run):
    return mean_stage_ms(run, "t_prepared", "t_dispatched")
