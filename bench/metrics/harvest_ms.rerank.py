"""Admission and batching (``serve/engine.py``): mean over the window's
batches of ``t_delivered - t_ready`` (ms): the copies to the host, the
completions built, and their delivery (the futures' callbacks run
there)."""
from bench.stages import mean_stage_ms


def read(run):
    return mean_stage_ms(run, "t_ready", "t_delivered")
