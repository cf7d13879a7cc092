"""Admission and batching (``serve/engine.py``): mean over the window's
batches of ``t_prepared - t_release`` (ms): bucketing, padding and the
device copies of the batch's arguments, on the admit thread."""
from bench.stages import mean_stage_ms


def read(run):
    return mean_stage_ms(run, "t_release", "t_prepared")
